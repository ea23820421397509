"""The train step names its layers: named scopes reach the compiled HLO as
op metadata, which a device profile reads per layer."""
import jax
import pytest

from repro.configs import registry
from repro.core.collectives import GZConfig
from repro.launch.shapes import InputShape, train_specs
from repro.launch.training import (GRAD_SYNC_SCOPE, OPTIMIZER_SCOPE,
                                   make_setup, make_train_step)
from repro.models.model import BLOCKS_SCOPE, HEAD_SCOPE
from repro.models.parallel import init_params
from repro.optim.adamw import adamw_init

import _scopes

BATCH, SEQ = 2, 64


def _step(**setup_kwargs):
    cfg = registry.get("mamba2-780m", smoke=True)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    setup = make_setup(cfg, mesh, **setup_kwargs)
    _, bspecs = train_specs(cfg, InputShape("t", SEQ, BATCH, "train"), mesh)
    params = init_params(setup.defs, jax.random.key(0))
    opt = adamw_init(params)
    batch = {k: jax.ShapeDtypeStruct(s, jax.numpy.int32)
             for k, s in (("tokens", (BATCH, SEQ)), ("labels", (BATCH, SEQ)))}
    step = make_train_step(setup, bspecs)
    return step, params, opt, batch


def _scopes_seen(step, params, opt, batch) -> set:
    """(scope, transposed) of every instruction of the compiled step."""
    text = step.lower(params, opt, batch).compile().as_text()
    return {entry for op_name, _ in _scopes.op_names(text).values()
            for entry in _scopes.path(op_name)}


def test_step_ops_sit_in_layer_scopes():
    step, params, opt, batch = _step()
    seen = _scopes_seen(step, params, opt, batch)
    assert (BLOCKS_SCOPE, False) in seen  # forward layer stack
    assert (BLOCKS_SCOPE, True) in seen   # its backward, recompute included
    assert {s for s, _ in seen} >= {HEAD_SCOPE, OPTIMIZER_SCOPE}


@pytest.mark.parametrize("overlap_sync", [False, True])
def test_gz_synced_step_carries_the_grad_sync_scope(overlap_sync):
    step, params, opt, batch = _step(
        grad_gz=GZConfig(eb=1e-4), skip_on_overflow=True,
        overlap_sync=overlap_sync)
    seen = {s for s, _ in _scopes_seen(step, params, opt, batch)}
    assert GRAD_SYNC_SCOPE in seen


def test_step_metrics_have_no_modeled_overlap():
    step, params, opt, batch = _step()
    _, _, metrics = jax.eval_shape(step, params, opt, batch)
    assert set(metrics) == {"loss", "gnorm", "lr", "skipped"}
