"""``correct`` on the CPU at the program's smoke widths: a sound run
passes, each fault of the timed path fails it, and so does the float8
control put in the program's place. The harness's look for a chip is
skipped; the rest of a run is driven as on the chip."""
import time

import jax
import pytest

from bench_tiny import LIMITS, make_root, smoke_model_config

from bench import check, harness, program
from bench.faults import half_batch, state_unchanged

SEED = 2**31 + 4242


def _run(tmp_path, monkeypatch, kind, fault=None):
    bench = make_root(tmp_path, kind)
    monkeypatch.setattr(program, "model_config", smoke_model_config)
    if fault is not None:
        real = program.build

        def broken(config, traffic, devices):
            built = real(config, traffic, devices)
            built.step = fault(built)
            return built

        monkeypatch.setattr(program, "build", broken)
    return harness.run("tiny", SEED, 0.2, False,
                       t_process=time.perf_counter(), require_tpu=False,
                       bench_dir=bench)


@pytest.mark.parametrize("kind", ["dense", "ssm"])
def test_sound_run_is_correct(tmp_path, monkeypatch, kind):
    out = _run(tmp_path, monkeypatch, kind)
    assert out["correct"], out["checks"]
    assert out["metrics"]["tokens_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [state_unchanged, half_batch],
                         ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("kind", ["dense", "ssm"])
def test_fault_is_not_correct(tmp_path, monkeypatch, kind, fault):
    out = _run(tmp_path, monkeypatch, kind, fault)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("kind", ["dense", "ssm"])
def test_float8_control_is_not_correct(tmp_path, monkeypatch, kind):
    bench = make_root(tmp_path, kind)
    monkeypatch.setattr(program, "model_config", smoke_model_config)
    cell = harness.load_cell("tiny", bench)
    devs = jax.devices()[:1]
    built = program.build(cell.config, cell.traffic, devs)
    with program.set_mesh(built.mesh):
        params, opt, gen, bits = harness.make_state(cell, built, SEED)
        _, _, prog, batches = harness.first_steps(built, gen, params, opt,
                                                  SEED)
    args = (cell.config["family"], cell.config, cell.traffic,
            built.abstract_params, SEED, batches, bits[:check.STEPS], devs)
    ref = check.reference_readings(*args)
    ctl = check.reference_readings(*args, precision="float8")
    assert check.judge(check.compare(prog, ref), LIMITS[kind])[0]
    assert not check.judge(check.compare(ctl, ref), LIMITS[kind])[0]
