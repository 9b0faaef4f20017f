"""``correct`` of the four-node MATCHA cell on the CPU at the program's
smoke widths, on four virtual devices in a child process: a sound run
passes; a step that returns its state unchanged, one that leaves out
half of the batch, and one that leaves out the exchange between nodes
each fail it."""
import json
import os
import subprocess
import sys
import time

import pytest

from bench_tiny import REPO

CASES = ("sound", "state_unchanged", "half_batch", "no_exchange")


def _cases():
    import jax

    from bench_tiny import make_root, smoke_model_config
    from test_bench_correct import SEED

    from bench import harness, program
    from bench.faults import half_batch, state_unchanged
    from repro.dist import decen_train as dt
    from repro.optim.optimizers import sgd

    def no_exchange(built):
        return dt.make_train_step(built.model, sgd(0.05, momentum=0.9), None,
                                  built.spec, gossip_mode="none")

    faults = {"sound": None, "state_unchanged": state_unchanged,
              "half_batch": half_batch, "no_exchange": no_exchange}
    import tempfile
    from pathlib import Path

    program.model_config = smoke_model_config
    real = program.build
    out = {}
    assert len(jax.devices()) == 4
    for name in CASES:
        fault = faults[name]

        def build(config, traffic, devices, fault=fault):
            built = real(config, traffic, devices)
            if fault is not None:
                built.step = fault(built)
            return built

        program.build = build
        with tempfile.TemporaryDirectory() as tmp:
            bench = make_root(Path(tmp), "ring")
            res = harness.run("tiny", SEED, 0.2, False,
                              t_process=time.perf_counter(),
                              require_tpu=False, bench_dir=bench)
        out[name] = {"correct": res["correct"], "checks": res["checks"]}
    return out


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(REPO / "bench" / "tests"), str(REPO / "src"),
                    str(REPO)]))
    proc = subprocess.run([sys.executable, __file__], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_ring_run_is_correct(results):
    assert results["sound"]["correct"], results["sound"]["checks"]


@pytest.mark.parametrize("fault", CASES[1:])
def test_ring_fault_is_not_correct(results, fault):
    assert not results[fault]["correct"], results[fault]["checks"]


if __name__ == "__main__":
    print(json.dumps(_cases()))
