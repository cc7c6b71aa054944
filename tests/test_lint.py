"""trc-lint suite (tpu_render_cluster/lint): the codebase-native static
analysis layer, gated in tier-1.

Two halves, same shape as the metric naming lint (test_telemetry.py):

- fixture snippets that MUST fire — one positive and one
  pragma-suppressed negative per pass, asserting the finding's exact
  file:line — prove each pass actually detects its defect class;
- the whole-package clean run is the gate: every real finding the passes
  surface has been fixed (or carries a reasoned pragma), and drift in
  README/PROTOCOL/the registries fails tier-1 the moment it lands.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from tpu_render_cluster.lint import PASSES, lint_package
from tpu_render_cluster.lint.core import LintContext, run_lint
from tpu_render_cluster.protocol.schema import WIRE_SCHEMAS, WireSchema
from tpu_render_cluster.utils.env import ENV_VARS, EnvVar

pytestmark = pytest.mark.lint

REPO_ROOT = Path(__file__).resolve().parent.parent


def make_ctx(tmp_path: Path, files: dict[str, str], **overrides) -> LintContext:
    """Write a fixture package tree and build a context over it."""
    package_root = tmp_path / "fixpkg"
    package_root.mkdir(exist_ok=True)
    for rel, body in files.items():
        path = package_root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(body))
    return LintContext.for_package(package_root, tmp_path, **overrides)


def run_pass(ctx: LintContext, pass_id: str):
    return run_lint(ctx, PASSES, (pass_id,)).findings


# ---------------------------------------------------------------------------
# loop-blocking


LOOP_POSITIVE = """\
    import asyncio
    import os
    import time


    def _journal(record):
        handle = open("/tmp/x", "a")
        handle.write(record)
        os.fsync(handle.fileno())


    async def dispatch_loop():
        time.sleep(0.5)
        _journal("unit-finished")
"""


def test_loop_blocking_fires_with_exact_lines(tmp_path):
    ctx = make_ctx(tmp_path, {"svc.py": LOOP_POSITIVE})
    findings = run_pass(ctx, "loop-blocking")
    by_line = {(f.path, f.line) for f in findings}
    # Direct blocking call in the coroutine body: time.sleep at line 13.
    assert ("fixpkg/svc.py", 13) in by_line
    # Reachable chain: the _journal() call site (line 14) reaches both the
    # open() and the fsync inside the helper.
    chained = [f for f in findings if f.line == 14 and f.path == "fixpkg/svc.py"]
    descs = {f.message for f in chained}
    assert any("os.fsync()" in d for d in descs)
    assert any("open()" in d for d in descs)
    # The chain names the blocking site's true location.
    assert any("fixpkg/svc.py:9" in f.message for f in chained)


def test_loop_blocking_to_thread_hop_is_clean(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "svc.py": """\
    import asyncio
    import os


    def _journal(record):
        os.fsync(3)


    async def dispatch_loop():
        await asyncio.to_thread(_journal, "unit-finished")
    """
        },
    )
    assert run_pass(ctx, "loop-blocking") == []


def test_loop_blocking_pragma_suppresses_and_requires_reason(tmp_path):
    body = """\
    import time


    async def teardown():
        time.sleep(0.1)  # trc-lint: disable=loop-blocking (shutdown path; the loop serves nothing afterwards)
    """
    ctx = make_ctx(tmp_path, {"svc.py": body})
    assert run_pass(ctx, "loop-blocking") == []

    reasonless = body.replace(
        " (shutdown path; the loop serves nothing afterwards)", ""
    )
    ctx = make_ctx(tmp_path, {"svc.py": reasonless})
    findings = run_pass(ctx, "loop-blocking")
    # The suppression still applies, but the missing reason is itself a
    # finding — "green" forces every suppression to be explained.
    assert [f.pass_id for f in findings] == ["pragma"]
    assert "without a reason" in findings[0].message


def test_pragma_reason_may_contain_parentheses(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "svc.py": """\
    import time


    async def teardown():
        time.sleep(0.1)  # trc-lint: disable=loop-blocking (teardown (no loop work pending) accepts the stall)
    """
        },
    )
    assert run_pass(ctx, "loop-blocking") == []


def test_loop_blocking_chain_site_pragma_covers_every_caller(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "svc.py": """\
    import os


    def _journal(record):
        os.fsync(3)  # trc-lint: disable=loop-blocking (test: callers accept the stall)


    async def a():
        _journal("x")


    async def b():
        _journal("y")
    """
        },
    )
    assert run_pass(ctx, "loop-blocking") == []


# ---------------------------------------------------------------------------
# wire-schema


WIRE_FIXTURE_REGISTRY = {
    "fix_message": WireSchema(
        "fix_message", "M->W", required=("alpha",), optional=("beta",)
    )
}

WIRE_POSITIVE = """\
    from typing import Any, ClassVar


    class FixMessage:
        type_name: ClassVar[str] = "fix_message"
        alpha: int
        beta: int | None = None

        def to_payload(self) -> dict[str, Any]:
            return {"alpha": self.alpha, "beta": self.beta}

        @classmethod
        def from_payload(cls, payload: dict[str, Any]) -> "FixMessage":
            return cls(payload["alpha"], payload.get("beta"))
"""


def test_wire_schema_flags_unconditional_optional_key(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {"fixmessages.py": WIRE_POSITIVE},
        wire_registry=WIRE_FIXTURE_REGISTRY,
        messages_module_suffix="fixmessages",
        protocol_text="",
    )
    findings = run_pass(ctx, "wire-schema")
    assert len(findings) == 1
    finding = findings[0]
    assert finding.path == "fixpkg/fixmessages.py" and finding.line == 10
    assert "'beta'" in finding.message and "omitted-when-absent" in finding.message


def test_wire_schema_conforming_class_is_clean_and_pragma_suppresses(tmp_path):
    conforming = """\
    from typing import Any, ClassVar


    class FixMessage:
        type_name: ClassVar[str] = "fix_message"

        def to_payload(self) -> dict[str, Any]:
            out: dict[str, Any] = {"alpha": self.alpha}
            if self.beta is not None:
                out["beta"] = self.beta
            return out

        @classmethod
        def from_payload(cls, payload: dict[str, Any]) -> "FixMessage":
            return cls(payload["alpha"], payload.get("beta"))
    """
    ctx = make_ctx(
        tmp_path,
        {"fixmessages.py": conforming},
        wire_registry=WIRE_FIXTURE_REGISTRY,
        messages_module_suffix="fixmessages",
        protocol_text="",
    )
    assert run_pass(ctx, "wire-schema") == []

    suppressed = WIRE_POSITIVE.replace(
        'return {"alpha": self.alpha, "beta": self.beta}',
        'return {"alpha": self.alpha, "beta": self.beta}  '
        "# trc-lint: disable=wire-schema (fixture: not a real wire class)",
    )
    ctx = make_ctx(
        tmp_path,
        {"fixmessages.py": suppressed},
        wire_registry=WIRE_FIXTURE_REGISTRY,
        messages_module_suffix="fixmessages",
        protocol_text="",
    )
    assert run_pass(ctx, "wire-schema") == []


def test_wire_schema_checks_protocol_md_rows(tmp_path):
    conforming = """\
    from typing import Any, ClassVar


    class FixMessage:
        type_name: ClassVar[str] = "fix_message"

        def to_payload(self) -> dict[str, Any]:
            out: dict[str, Any] = {"alpha": self.alpha}
            if self.beta is not None:
                out["beta"] = self.beta
            return out

        @classmethod
        def from_payload(cls, payload: dict[str, Any]) -> "FixMessage":
            return cls(payload["alpha"], payload.get("beta"))
    """
    doc = (
        "| Wire tag | Direction | Payload highlights |\n"
        "|---|---|---|\n"
        "| `fix_message` | M→W | `alpha` only |\n"
    )
    ctx = make_ctx(
        tmp_path,
        {"fixmessages.py": conforming},
        wire_registry=WIRE_FIXTURE_REGISTRY,
        messages_module_suffix="fixmessages",
        protocol_text=doc,
    )
    findings = run_pass(ctx, "wire-schema")
    assert len(findings) == 1
    assert findings[0].path == "PROTOCOL.md" and findings[0].line == 3
    assert "`beta`" in findings[0].message


# ---------------------------------------------------------------------------
# jit-purity


JIT_POSITIVE = """\
    import time

    import jax


    @jax.jit
    def render_step(x):
        t0 = time.time()
        return x * t0
"""


def test_jit_purity_fires_on_decorated_function(tmp_path):
    ctx = make_ctx(tmp_path, {"kern.py": JIT_POSITIVE})
    findings = run_pass(ctx, "jit-purity")
    assert len(findings) == 1
    assert (findings[0].path, findings[0].line) == ("fixpkg/kern.py", 8)
    assert "time.time()" in findings[0].message


def test_jit_purity_fires_on_factory_returned_function(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "kern.py": """\
    import numpy as np

    import jax


    def make_renderer(scene):
        table = np.asarray(scene)  # host code: fine

        def render(x):
            noise = np.random.uniform(size=3)
            return x + noise

        return render


    renderer = jax.jit(make_renderer("s"))
    """
        },
    )
    findings = run_pass(ctx, "jit-purity")
    assert len(findings) == 1
    assert (findings[0].path, findings[0].line) == ("fixpkg/kern.py", 10)
    assert "np.random" in findings[0].message


def test_jit_purity_pragma_suppressed_negative(tmp_path):
    suppressed = JIT_POSITIVE.replace(
        "t0 = time.time()",
        "t0 = time.time()  # trc-lint: disable=jit-purity "
        "(fixture: trace-time stamp is the point of this test)",
    )
    ctx = make_ctx(tmp_path, {"kern.py": suppressed})
    assert run_pass(ctx, "jit-purity") == []


# ---------------------------------------------------------------------------
# env-registry


def test_env_registry_flags_direct_environ_read(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "knobs.py": """\
    import os

    WIDTH = os.environ.get("TRC_FIXTURE_WIDTH", "8")
    """
        },
        env_registry={},
        readme_text="",
    )
    findings = run_pass(ctx, "env-registry")
    assert len(findings) == 1
    assert (findings[0].path, findings[0].line) == ("fixpkg/knobs.py", 3)
    assert "TRC_FIXTURE_WIDTH" in findings[0].message


def test_env_registry_flags_undeclared_helper_read_and_pragma(tmp_path):
    body = """\
    from tpu_render_cluster.utils.env import env_int

    WIDTH = env_int("TRC_FIXTURE_WIDTH", 8)
    """
    ctx = make_ctx(
        tmp_path, {"knobs.py": body}, env_registry={}, readme_text=""
    )
    findings = run_pass(ctx, "env-registry")
    assert len(findings) == 1
    assert (findings[0].path, findings[0].line) == ("fixpkg/knobs.py", 3)
    assert "undeclared TRC_FIXTURE_WIDTH" in findings[0].message

    suppressed = body.replace(
        'env_int("TRC_FIXTURE_WIDTH", 8)',
        'env_int("TRC_FIXTURE_WIDTH", 8)  '
        "# trc-lint: disable=env-registry (fixture knob, not part of the registry)",
    )
    ctx = make_ctx(
        tmp_path, {"knobs.py": suppressed}, env_registry={}, readme_text=""
    )
    assert run_pass(ctx, "env-registry") == []


def test_env_registry_flags_dead_and_undocumented_declarations(tmp_path):
    registry = {
        "TRC_FIXTURE_DEAD": EnvVar("TRC_FIXTURE_DEAD", "int", 1, "unused"),
    }
    ctx = make_ctx(
        tmp_path,
        {"knobs.py": "X = 1\n"},
        env_registry=registry,
        readme_text="| `TRC_FIXTURE_GHOST` | int | documented but undeclared |\n",
    )
    messages = [f.message for f in run_pass(ctx, "env-registry")]
    assert any(
        "TRC_FIXTURE_DEAD" in m and "nothing in the package reads" in m
        for m in messages
    )
    assert any(
        "TRC_FIXTURE_DEAD" in m and "missing from README" in m for m in messages
    )
    assert any(
        "TRC_FIXTURE_GHOST" in m and "does not declare" in m for m in messages
    )


# ---------------------------------------------------------------------------
# env-tiers


ENV_TIERS_POSITIVE = """\
    import functools

    import jax

    from tpu_render_cluster.render.pallas_kernels import bvh_quant_mode


    @functools.partial(jax.jit, static_argnames=("width",))
    def render_batch(frames, *, width):
        quant = bvh_quant_mode()
        return frames * quant
"""


def test_env_tiers_fires_inside_traced_function(tmp_path):
    ctx = make_ctx(tmp_path, {"kern.py": ENV_TIERS_POSITIVE})
    findings = run_pass(ctx, "env-tiers")
    assert len(findings) == 1
    assert (findings[0].path, findings[0].line) == ("fixpkg/kern.py", 10)
    assert "bvh_quant_mode" in findings[0].message
    assert "static argument" in findings[0].message


def test_env_tiers_threaded_static_arg_is_clean(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "kern.py": """\
    import functools

    import jax

    from tpu_render_cluster.render.pallas_kernels import bvh_quant_mode


    @functools.partial(jax.jit, static_argnames=("quant",))
    def render_batch(frames, *, quant):
        return frames * quant


    def driver(frames):
        # Untraced driver: resolving the tier HERE is the contract.
        return render_batch(frames, quant=bvh_quant_mode())
    """
        },
    )
    assert run_pass(ctx, "env-tiers") == []


def test_env_tiers_pragma_suppressed_negative(tmp_path):
    suppressed = ENV_TIERS_POSITIVE.replace(
        "quant = bvh_quant_mode()",
        "quant = bvh_quant_mode()  # trc-lint: disable=env-tiers "
        "(fixture: baking the tier is this test's point)",
    )
    ctx = make_ctx(tmp_path, {"kern.py": suppressed})
    assert run_pass(ctx, "env-tiers") == []


# ---------------------------------------------------------------------------
# pragma meta-pass


def test_pragma_unknown_pass_and_unused_suppression_fire(tmp_path):
    ctx = make_ctx(
        tmp_path,
        {
            "mod.py": """\
    X = 1  # trc-lint: disable=no-such-pass (typo'd pass id)
    Y = 2  # trc-lint: disable=loop-blocking (nothing here blocks)
    """
        },
    )
    findings = run_lint(ctx, PASSES).findings
    assert any("unknown pass" in f.message and f.line == 1 for f in findings)
    assert any("suppresses nothing" in f.message and f.line == 2 for f in findings)


# ---------------------------------------------------------------------------
# the real registries + the codebase-wide gate


def test_wire_registry_matches_message_classes():
    from tpu_render_cluster.protocol.messages import ALL_MESSAGE_TYPES

    assert {m.type_name for m in ALL_MESSAGE_TYPES} == set(WIRE_SCHEMAS)


def test_env_registry_declares_every_helper_default():
    # Spot-check shape: every declaration carries a kind and a doc line.
    assert len(ENV_VARS) >= 58
    for var in ENV_VARS.values():
        assert var.kind in ("int", "float", "str", "flag", "path", "port", "spec")
        assert var.doc


def test_no_option_selects_an_execution_tier():
    """69 declared options, none of them a way to choose how a frame is
    rendered: that is decided by the work unit and the worker's sharding."""
    assert len(ENV_VARS) == 69
    assert not [name for name in ENV_VARS if "WAVEFRONT" in name or "RAYPOOL" in name]


def test_repo_is_lint_clean():
    """THE gate: the four passes + pragma meta-pass over the whole package,
    cross-checked against the real README.md / PROTOCOL.md. Every real
    finding was fixed in the PR that introduced the suite; any regression
    (a blocking call on the loop, a null-serialized optional key, an
    undeclared or undocumented TRC_* knob, an unexplained suppression)
    fails here with its file:line."""
    report = lint_package()
    assert report.files_scanned > 100
    assert report.ok, "\n" + report.format()


def test_cli_text_and_json_and_exit_codes(tmp_path):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": "/tmp"}
    clean = subprocess.run(
        [sys.executable, "-m", "tpu_render_cluster.lint", "--json"],
        capture_output=True,
        text=True,
        cwd=REPO_ROOT,
        env=env,
        timeout=180,
    )
    assert clean.returncode == 0, clean.stdout + clean.stderr
    report = json.loads(clean.stdout)
    assert report["ok"] is True and report["findings"] == []
    assert set(report["counts"]) == set()

    # A deliberately-broken fixture package through the SAME CLI must exit
    # nonzero and report the finding with its file:line.
    package = tmp_path / "badpkg"
    package.mkdir()
    (package / "svc.py").write_text(
        "import time\n\n\nasync def loop():\n    time.sleep(1)\n"
    )
    broken = subprocess.run(
        [
            sys.executable,
            "-m",
            "tpu_render_cluster.lint",
            "--package-root",
            str(package),
            "--repo-root",
            str(tmp_path),
            "--passes",
            "loop-blocking",
            "--json",
        ],
        capture_output=True,
        text=True,
        cwd=REPO_ROOT,
        env=env,
        timeout=180,
    )
    assert broken.returncode == 1
    report = json.loads(broken.stdout)
    assert report["counts"] == {"loop-blocking": 1}
    finding = report["findings"][0]
    assert finding["path"] == "badpkg/svc.py" and finding["line"] == 5


def test_standalone_script_runs_from_bare_checkout(tmp_path):
    """scripts/lint.py must work with no package install and an arbitrary
    cwd (the validate_trace.py contract)."""
    probe = subprocess.run(
        [sys.executable, str(REPO_ROOT / "scripts" / "lint.py"), "--list-passes"],
        capture_output=True,
        text=True,
        cwd=tmp_path,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin", "HOME": "/tmp"},
        timeout=120,
    )
    assert probe.returncode == 0, probe.stdout + probe.stderr
    for pass_id in PASSES:
        assert pass_id in probe.stdout
