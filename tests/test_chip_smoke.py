"""chip_smoke.py must not rot between chip runs: walk it on the CPU at
rehearsal size (as a CPU child — the script owns its process), and pin
the contract around it — a rehearsal is never reported as a chip run, a
run that finds no TPU fails, and the compile cache lands where the
environment or the checkout says."""

import json
import os
import subprocess
import sys

import pytest

from jepsen_jgroups_raft_tpu import platform as plat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

ONE_CHIP_PHASES = [
    "gate", "compile-cache", "synth", "library-default",
    "library-forced-kernel", "library-host-reference", "service",
    "family-mask", "family-sort", "family-cycle",
    "compile-cache-after", "done"]
FOUR_CHIP_PHASES = ["gate", "compile-cache", "mesh", "compile-cache-after",
                    "done"]


def run_smoke(args, n_devices, cwd):
    env = plat.cpu_subprocess_env()
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, SMOKE, *args], env=env, cwd=cwd,
                         capture_output=True, text=True, timeout=600)
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    return out, lines


@pytest.mark.parametrize("args,n_devices,phases", [
    (["--rehearse"], 1, ONE_CHIP_PHASES),
    (["--rehearse", "--chips", "4"], 4, FOUR_CHIP_PHASES),
], ids=["one-chip", "four-chips"])
def test_rehearsal_walks_every_phase_and_is_never_ok(tmp_path, args,
                                                     n_devices, phases):
    out, lines = run_smoke(args, n_devices, tmp_path)
    assert out.returncode != 0, out.stdout[-2000:]
    assert [ln["phase"] for ln in lines[:-1]] == phases, (
        out.stdout[-3000:] + out.stderr[-3000:])
    last = lines[-1]
    assert last["ok"] is False and last["reason"] == "rehearsal", last
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == n_devices
    # the last line of standard output IS the verdict object
    assert json.loads(out.stdout.strip().splitlines()[-1]) == last


def test_a_run_that_finds_no_tpu_fails_at_the_gate(tmp_path):
    out, lines = run_smoke([], 1, tmp_path)
    assert out.returncode != 0
    assert [ln.get("phase") for ln in lines] == ["gate", "gate"], out.stdout
    assert lines[-1]["ok"] is False and "no TPU" in lines[-1]["reason"]


class TestCompileCache:
    def test_env_dir_wins_and_nothing_is_set_in_code(self, monkeypatch):
        import jax

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
        monkeypatch.setattr(plat, "_cpu_pinned", lambda: False)
        monkeypatch.setattr(
            jax.config, "update",
            lambda *a, **k: pytest.fail(f"config set in code: {a}"))
        assert plat.enable_compile_cache() == "/x"

    def test_unset_it_lands_in_the_checkout(self, monkeypatch):
        import jax

        calls = []
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(plat, "_cpu_pinned", lambda: False)
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: calls.append(a))
        want = os.path.join(REPO, ".jax_cache")
        assert plat.enable_compile_cache() == want
        assert calls == [("jax_compilation_cache_dir", want)]

    def test_a_cpu_pinned_process_gets_none(self, monkeypatch):
        import jax

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(
            jax.config, "update",
            lambda *a, **k: pytest.fail(f"config set in code: {a}"))
        assert plat.enable_compile_cache() is None  # conftest pins cpu
