"""Start-up contracts of the chip bring-up: where the compile cache goes,
that chip_smoke.py never runs on the CPU by accident, that the multi-chip
dry run refuses to switch platforms, and that a configuration the kernels
cannot serve says so.
"""
import json
import os
import shutil
import subprocess
import sys
import warnings

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, env_extra=None, env_drop=(), cwd=ROOT, timeout=600):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, env=env, cwd=cwd, timeout=timeout)


class TestCompileCachePlacement:
    """kubernetes_tpu.ops places the persistent compile cache on import:
    from outside when JAX_COMPILATION_CACHE_DIR is set (code sets nothing),
    else at the fixed in-checkout path."""

    PROBE = ("import kubernetes_tpu.ops as o, jax; "
             "print(jax.config.jax_compilation_cache_dir); "
             "print(o.DEFAULT_COMPILE_CACHE_DIR)")

    def test_unset_env_uses_fixed_in_checkout_path(self):
        p = _run(["-c", self.PROBE], env_drop=("JAX_COMPILATION_CACHE_DIR",))
        assert p.returncode == 0, p.stderr
        got, default = p.stdout.split()
        assert got == default == os.path.join(ROOT, ".jax_cache")

    def test_env_set_means_code_sets_nothing(self, tmp_path):
        outside = str(tmp_path / "cache-from-outside")
        p = _run(["-c", self.PROBE],
                 env_extra={"JAX_COMPILATION_CACHE_DIR": outside})
        assert p.returncode == 0, p.stderr
        got, default = p.stdout.split()
        assert got == outside and got != default

    def test_no_other_cache_path_in_the_tree(self):
        hits = []
        for base, _dirs, files in os.walk(os.path.join(ROOT,
                                                       "kubernetes_tpu")):
            for f in files:
                if f.endswith(".py"):
                    path = os.path.join(base, f)
                    with open(path) as fh:
                        if "jax_compilation_cache_dir" in fh.read():
                            hits.append(os.path.relpath(path, ROOT))
        assert hits == [os.path.join("kubernetes_tpu", "ops",
                                     "__init__.py")]


class TestChipSmokeRefusesTheCpu:
    def test_default_invocation_on_cpu_exits_nonzero_naming_platform(self):
        p = _run([SMOKE], env_extra={"JAX_PLATFORMS": "cpu"})
        assert p.returncode != 0
        assert "'cpu'" in p.stderr and "tpu" in p.stderr
        assert "{" not in p.stdout          # no result line of any kind

    def test_alone_in_a_directory_exits_nonzero_without_result(self,
                                                               tmp_path):
        shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
        p = _run(["chip_smoke.py"], cwd=str(tmp_path),
                 env_drop=("PYTHONPATH",))
        assert p.returncode != 0
        assert "{" not in p.stdout

    def test_cpu_rehearsal_drives_every_stage_and_is_never_ok(self):
        """The explicit rehearsal keeps the script from rotting between
        chip runs: every stage runs at a tiny size on the CPU backend and
        every check passes, yet the result can never read as a chip pass."""
        p = _run([SMOKE, "--rehearse-cpu"],
                 env_extra={"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
        assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
        out = json.loads(p.stdout.strip().splitlines()[-1])
        # the report is the last line: a rehearsal prints no verdict
        assert "ok" not in out and out["rehearsal"] is True
        assert out["checks_passed"] and not out["failed_checks"]
        assert out["device"]["platform"] == "cpu"
        assert out["claim"] is None
        for stage in ("drain", "lanes.plain", "lanes.spread", "lanes.gang",
                      "lanes.preempt", "lanes.preempt_scan", "serial",
                      "serve"):
            assert stage in out["stages"], stage


class TestProvisionRaisesNotSwitches:
    def test_enough_host_devices_passes(self):
        import __graft_entry__ as g
        g._provision_devices(8)             # conftest gives 8 CPU devices

    def test_too_few_devices_raises_and_says_how_to_start(self):
        import jax
        import __graft_entry__ as g
        before = jax.devices()
        with pytest.raises(RuntimeError) as e:
            g._provision_devices(len(before) + 1)
        msg = str(e.value)
        assert "JAX_PLATFORMS=cpu" in msg
        assert "xla_force_host_platform_device_count" in msg
        assert jax.devices() == before      # the live process is untouched


class TestFactorySaysWhenItLeavesTheDevice:
    def test_unsupported_priority_warns(self):
        from kubernetes_tpu.apis.config import (AlgorithmSource,
                                                SchedulerConfiguration)
        from kubernetes_tpu.core.tpu_scheduler import TPUScheduler
        from kubernetes_tpu.factory import create_scheduler
        from kubernetes_tpu.store.store import Store
        cfg = SchedulerConfiguration(algorithm_source=AlgorithmSource(
            provider=None, policy_inline={"priorities": [
                {"name": "EqualPriority", "weight": 1}]}))
        with pytest.warns(UserWarning, match="no kernel implementation"):
            sched = create_scheduler(Store(), cfg)
        assert not isinstance(sched.algorithm, TPUScheduler)

    def test_default_configuration_is_silent_and_on_device(self):
        from kubernetes_tpu.core.tpu_scheduler import TPUScheduler
        from kubernetes_tpu.factory import create_scheduler
        from kubernetes_tpu.store.store import Store
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            sched = create_scheduler(Store())
        assert isinstance(sched.algorithm, TPUScheduler)
