"""A whole run with the timed path broken underneath reads correct=false;
the same run unbroken reads correct=true. Each cell's control fails its
comparison. All at small sizes on the CPU, past the harness's look for a
chip."""
import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests import small

CODEC = "codec.lorenzo.16MiB.smooth"
ALLREDUCE = "allreduce.ring.64MiB.smooth"
TRAIN1 = "train.mamba2-780m.1chip"


def _alter_decompress(monkeypatch, how):
    from repro.core.compressor import ErrorBoundedLorenzo

    orig = ErrorBoundedLorenzo.decompress

    def broken(self, c):
        y = orig(self, c)
        if how == "altered":
            return y.at[7].add(5e-4)
        return y.at[y.shape[0] // 2:].set(0.0)  # half the values left out

    monkeypatch.setattr(ErrorBoundedLorenzo, "decompress", broken)


def _alter_allreduce(monkeypatch, how):
    from repro.core.comm import CollectiveResult, GZCommunicator

    orig = GZCommunicator.allreduce

    def broken(self, x, **kw):
        res = orig(self, x, **kw)
        if how == "no_exchange":
            value = x
        elif how == "altered":
            value = res.value.at[3].add(5e-4)
        else:  # the second half of the payload left unreduced
            value = res.value.at[x.shape[0] // 2:].set(x[x.shape[0] // 2:])
        return CollectiveResult(value, res.overflow, res.nonfinite,
                                res.wire_bytes, res.ratio)

    monkeypatch.setattr(GZCommunicator, "allreduce", broken)


@pytest.mark.parametrize("name", [CODEC, ALLREDUCE])
def test_sound_collective_runs_are_correct(name):
    result, _ = small.run(name)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("how", ["altered", "half"])
def test_codec_faults_are_not_correct(monkeypatch, how):
    _alter_decompress(monkeypatch, how)
    result, _ = small.run(CODEC)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("how", ["no_exchange", "altered", "half"])
def test_allreduce_faults_are_not_correct(monkeypatch, how):
    _alter_allreduce(monkeypatch, how)
    result, _ = small.run(ALLREDUCE)
    assert not result["correct"], result["checks"]


def _train_driver(break_step):
    from bench.drivers import train

    class Broken(train.Driver):
        def _build_step(self, setup, bspecs):
            real = super()._build_step(setup, bspecs)
            return jax.jit(lambda p, o, b: break_step(real, p, o, b))

    return Broken


def _unchanged(real, p, o, b):
    _, _, m = real(p, o, b)
    return p, o, m


def _half_batch_driver():
    """The feed repeats the first half of each batch's rows in place of
    the second: the mean is taken over half the batch."""
    from bench.drivers import train

    class HalfBatch(train.Driver):
        def _next_batch(self):
            b = next(self.stream)
            half = b["tokens"].shape[0] // 2
            b = {k: v.copy() for k, v in b.items()}
            for v in b.values():
                v[half:] = v[:half]
            return jax.device_put(b, self.batch_sharding)

    return HalfBatch


def test_sound_training_run_is_correct():
    result, lines = small.run(TRAIN1)
    assert result["correct"], (result["checks"], lines)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_training_faults_are_not_correct(fault):
    driver = (_train_driver(_unchanged) if fault == "state_unchanged"
              else _half_batch_driver())
    result, lines = small.run(TRAIN1, driver_cls=driver)
    assert not result["correct"], (result["checks"], lines)


@pytest.mark.parametrize("name", [CODEC, ALLREDUCE, TRAIN1])
def test_controls_fail(name):
    cell = small.cell(name)
    module = harness.driver_module(cell)
    ctx = harness.Context(cell=cell, seed=2**32 + 3,
                          devices=jax.devices()[:cell.chips],
                          root=harness.ROOT, log=lambda s: None)
    checks = module.control(ctx)
    assert any(not c.ok for c in checks), checks


def test_half_batch_fault_planted_in_the_reference_fails():
    cell = small.cell(TRAIN1)
    module = harness.driver_module(cell)
    ctx = harness.Context(cell=cell, seed=2**32 + 5, devices=jax.devices()[:1],
                          root=harness.ROOT, log=lambda s: None)
    readings = module.faults(ctx)
    for fault in ("half_batch", "state_unchanged"):
        assert any(not c.ok for c in readings[fault]), (fault, readings)


def test_allreduce_traced_run_reports_its_layers():
    result, _ = small.run(ALLREDUCE, trace=True)
    assert result["correct"], result["checks"]
    assert {"hop_roofline.allreduce", "permute_ms.allreduce",
            "wire_MB.allreduce", "idle_share.allreduce"} <= set(result["metrics"])
