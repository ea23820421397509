"""Child script: validates shard_map gZ collectives on N virtual devices.

Run by tests/test_collectives_multidevice.py in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=<N> (must be set before
jax import, which is why this is a separate process).  N defaults to 8;
an explicit GZ_CHILD_DEVICES always wins, then a pre-set XLA_FLAGS
device count (_child_env.pin_device_count) — the CI non-power-of-two leg
runs the whole file at N=6.  Prints 'OK <name>' per passing check; any
assertion failure propagates as nonzero exit.
"""
from _child_env import pin_device_count

N = pin_device_count(8)

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.collectives import (
    GZConfig,
    gz_allgather,
    gz_allreduce,
    gz_broadcast,
    gz_reduce_scatter,
    gz_scatter,
)
from repro.core.shmap import shard_map

D = 1024 * N
mesh = jax.make_mesh((N,), ("x",))
rng = np.random.default_rng(0)
# smooth per-rank fields (paper's RTM-like regime)
base = np.cumsum(rng.normal(0, 0.01, (N, D)), axis=1).astype(np.float32)
exact_sum = base.sum(axis=0)

def shmap(f, in_specs, out_specs):
    return jax.jit(
        shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    )


def check_allreduce(algo, tol_hops):
    cfg = GZConfig(eb=1e-4, algo=algo, capacity_factor=1.2)
    def body(x):
        out, ovf = gz_allreduce(x[0], "x", cfg, return_info=True)
        return out[None], ovf[None]

    f = shmap(body, (P("x", None),), (P("x", None), P("x")))
    out, ovf = f(base)
    out = np.asarray(out)
    assert not np.asarray(ovf).any(), f"{algo}: capacity overflow"
    err = np.abs(out - exact_sum[None, :]).max()
    # worst-case budget guarantees <= eb total for redoub/ring;
    # intring is <= N*eb_total (single grid, N addends)
    bound = 1e-4 * tol_hops + np.abs(exact_sum).max() * 1e-6
    assert err <= bound, f"{algo}: err {err} > {bound}"
    spread = np.abs(out - out[0:1]).max()
    if algo == "intring":
        assert spread == 0.0, f"intring not bitwise consistent: {spread}"
    print(f"OK allreduce_{algo} err={err:.2e} spread={spread:.2e}")


check_allreduce("redoub", 1.05)
check_allreduce("ring", 1.05)
check_allreduce("intring", N * 1.05)

# reduce_scatter: rank r gets summed chunk r
cfg = GZConfig(eb=1e-4, capacity_factor=1.2)
f = shmap(lambda x: gz_reduce_scatter(x[0], "x", cfg), (P("x", None),), P("x"))
out = np.asarray(f(base)).reshape(N, D // N)
want = exact_sum.reshape(N, D // N)
err = np.abs(out - want).max()
assert err <= 1e-4 * 1.05 + np.abs(exact_sum).max() * 1e-6, err
print(f"OK reduce_scatter err={err:.2e}")

# allgather: every rank sees all chunks, one lossy hop
chunks = base[:, : D // N].copy()
f = shmap(lambda x: gz_allgather(x[0], "x", cfg)[None], (P("x", None),), P("x", None))
out = np.asarray(f(chunks)).reshape(N, N * (D // N))
want = chunks.reshape(-1)
err = np.abs(out - want[None]).max()
assert err <= 1e-4 * 1.001 + np.abs(want).max() * 2e-7, err
assert np.abs(out - out[0:1]).max() == 0.0  # identical on every rank
print(f"OK allgather err={err:.2e}")

# scatter from root 0: rank r gets chunk r within eb
full = np.cumsum(rng.normal(0, 0.01, N * D)).astype(np.float32)
xin = np.zeros((N, N * D), np.float32)
xin[0] = full  # root-significant input, replicated layout
f = shmap(lambda x: gz_scatter(x[0], "x", cfg), (P("x", None),), P("x"))
out = np.asarray(f(xin)).reshape(N, D)
err = np.abs(out - full.reshape(N, D)).max()
assert err <= 1e-4 * 1.001 + np.abs(full).max() * 2e-7, err
print(f"OK scatter err={err:.2e}")

# broadcast from root 0
xb = np.zeros((N, D), np.float32)
xb[0] = base[0]
f = shmap(lambda x: gz_broadcast(x[0], "x", cfg)[None], (P("x", None),), P("x", None))
out = np.asarray(f(xb))
err = np.abs(out - base[0][None]).max()
assert err <= 1e-4 * 1.001 + np.abs(base[0]).max() * 2e-7, err
assert np.abs(out - out[0:1]).max() == 0.0
print(f"OK broadcast err={err:.2e}")

# pipelined (chunked double-buffered) ring schedules: bitwise-identical to
# the sequential schedule when the sequential chunking is piece-aligned
# (DESIGN.md §4), and within budget always.
from repro.kernels import ops as _ops

D_ALIGNED = N * 2 * _ops.BLOCK * _ops.TILE_ROWS  # chunk = 2 whole-tile pieces
base_al = np.cumsum(rng.normal(0, 0.01, (N, D_ALIGNED)), axis=1).astype(np.float32)
outs = {}
for pc in (1, 2):
    cfg_p = GZConfig(eb=1e-4, algo="ring", capacity_factor=1.2, pipeline_chunks=pc)
    f = shmap(
        lambda x, c=cfg_p: gz_allreduce(x[0], "x", c, return_info=True)[0][None],
        (P("x", None),), P("x", None),
    )
    outs[pc] = np.asarray(f(base_al))
assert np.array_equal(outs[1], outs[2]), "pipelined ring != sequential (aligned)"
err = np.abs(outs[2] - base_al.sum(axis=0)[None]).max()
assert err <= 1e-4 * 1.05 + np.abs(base_al.sum(axis=0)).max() * 1e-6, err
print(f"OK allreduce_ring_pipelined bitwise==sequential, err={err:.2e}")

cfg_p = GZConfig(eb=1e-4, algo="ring", capacity_factor=1.2, pipeline_chunks=2)
f = shmap(lambda x: gz_reduce_scatter(x[0], "x", cfg_p), (P("x", None),), P("x"))
out = np.asarray(f(base)).reshape(N, D // N)
err = np.abs(out - exact_sum.reshape(N, D // N)).max()
assert err <= 1e-4 * 1.05 + np.abs(exact_sum).max() * 1e-6, err
print(f"OK reduce_scatter_pipelined err={err:.2e}")

f = shmap(
    lambda x: gz_allgather(x[0], "x", cfg_p)[None], (P("x", None),), P("x", None)
)
out = np.asarray(f(chunks)).reshape(N, N * (D // N))
err = np.abs(out - chunks.reshape(-1)[None]).max()
assert err <= 1e-4 * 1.001 + np.abs(chunks).max() * 2e-7, err
assert np.abs(out - out[0:1]).max() == 0.0
print(f"OK allgather_pipelined err={err:.2e}")

f = shmap(lambda x: gz_scatter(x[0], "x", cfg_p), (P("x", None),), P("x"))
out = np.asarray(f(xin)).reshape(N, D)
err = np.abs(out - full.reshape(N, D)).max()
assert err <= 1e-4 * 1.001 + np.abs(full).max() * 2e-7, err
print(f"OK scatter_pipelined err={err:.2e}")

# Single-pass fused hop (ISSUE 2): the fused_hop=True schedules must be
# bitwise identical to the PR 1 two-kernel hop composition — same wire
# bytes at every hop implies the same f32 at every rank.  Checked on the
# sequential ring, the pipelined ring, redoub, and reduce_scatter.

def _run_allreduce(data, algo, fused_hop, pc=1):
    c = GZConfig(eb=1e-4, algo=algo, capacity_factor=1.2,
                 pipeline_chunks=pc, fused_hop=fused_hop)
    f = shmap(lambda x: gz_allreduce(x[0], "x", c)[None],
              (P("x", None),), P("x", None))
    return np.asarray(f(data))

for algo, pc, data in (("ring", 1, base), ("redoub", 1, base),
                       ("ring", 2, base_al), ("ring", 4, base_al)):
    a = _run_allreduce(data, algo, True, pc)
    b = _run_allreduce(data, algo, False, pc)
    assert np.array_equal(a, b), f"fused hop != two-kernel: {algo} P={pc}"
    print(f"OK fused_hop bitwise == two-kernel ({algo}, P={pc})")

cfg_fh = {}
for fh in (True, False):
    c = GZConfig(eb=1e-4, capacity_factor=1.2, pipeline_chunks=2, fused_hop=fh)
    f = shmap(lambda x, c=c: gz_reduce_scatter(x[0], "x", c), (P("x", None),), P("x"))
    cfg_fh[fh] = np.asarray(f(base))
assert np.array_equal(cfg_fh[True], cfg_fh[False])
print("OK fused_hop bitwise == two-kernel (reduce_scatter pipelined)")

# Overflow-flag propagation (ISSUE 2 satellite): a starved capacity_factor
# must trip the overflow bit on SOME hop of the pipelined schedules, and
# return_info must OR it across pieces and hops on every rank.  Rough
# (incompressible) data guarantees the streams genuinely overflow.
rough = rng.normal(0, 100.0, (N, D_ALIGNED)).astype(np.float32)
for algo, pc in (("ring", 2), ("ring", 1), ("redoub", 1)):
    cfg_tiny = GZConfig(eb=1e-6, algo=algo, capacity_factor=0.02,
                        pipeline_chunks=pc)
    f = shmap(
        lambda x, c=cfg_tiny: gz_allreduce(x[0], "x", c, return_info=True)[1][None],
        (P("x", None),), P("x"),
    )
    ovf = np.asarray(f(rough))
    assert ovf.all(), f"overflow not propagated: {algo} P={pc}"
    print(f"OK overflow propagated ({algo}, P={pc})")

cfg_tiny = GZConfig(eb=1e-6, capacity_factor=0.02, pipeline_chunks=2)
xin_rough = np.zeros((N, N * D), np.float32)
xin_rough[0] = rng.normal(0, 100.0, N * D).astype(np.float32)
f = shmap(
    lambda x: gz_scatter(x[0], "x", cfg_tiny, return_info=True)[1][None],
    (P("x", None),), P("x"),
)
assert np.asarray(f(xin_rough)).all(), "scatter overflow not propagated"
print("OK overflow propagated (scatter pipelined)")

# all_to_all: compressed vs exact (one lossy hop)
from repro.core.collectives import gz_all_to_all
x_a2a = base[:, : N * 512].reshape(N, N * 512).copy()
f = shmap(
    lambda x: gz_all_to_all(x[0], "x", cfg)[None], (P("x", None),), P("x", None)
)
got = np.asarray(f(x_a2a)).reshape(N, N, 512)
# rank r receives rank p's chunk r: want[r, p] = x_a2a[p, r*512:(r+1)*512]
want = x_a2a.reshape(N, N, 512).transpose(1, 0, 2)
err = np.abs(got - want).max()
assert err <= 1e-4 * 1.001 + np.abs(want).max() * 2e-7, err
print(f"OK all_to_all err={err:.2e}")

# ---------------------------------------------------------------------------
# Schedule-IR single authority (ISSUE 10): the device mesh and the
# global-view table replay walk the SAME route table, so the
# deterministic ops must agree np.array_equal-BITWISE — any divergence
# means execute and sim stopped reading one schedule.
# ---------------------------------------------------------------------------
from repro.core import simulator

sim_bc = np.stack(simulator.sim_broadcast_binomial(xb[0], N, cfg))
f = shmap(lambda x: gz_broadcast(x[0], "x", cfg)[None],
          (P("x", None),), P("x", None))
assert np.array_equal(np.asarray(f(xb)), sim_bc), \
    "broadcast: device != table replay"
print("OK schedule-IR bitwise parity (broadcast device == sim)")

sim_ag = np.stack(simulator.sim_allgather_ring(list(chunks), cfg))
f = shmap(lambda x: gz_allgather(x[0], "x", cfg)[None],
          (P("x", None),), P("x", None))
assert np.array_equal(np.asarray(f(chunks)).reshape(N, -1), sim_ag), \
    "allgather: device != table replay"
print("OK schedule-IR bitwise parity (allgather device == sim)")

# intring: both sides are bitwise rank-consistent on their own mesh and
# share ONE integer code grid, but the sim quantizes/dequantizes in f64
# while the device kernels stay f32 — rint at a code boundary can shift
# each rank's code by one, so the summed codes agree to within N (the
# observed gap is a single code), not bitwise.
cfg_int = GZConfig(eb=1e-4, algo="intring", capacity_factor=1.2)
sim_int = np.stack(simulator.sim_allreduce_intring(list(base), cfg_int))
f = shmap(lambda x: gz_allreduce(x[0], "x", cfg_int)[None],
          (P("x", None),), P("x", None))
dev_int = np.asarray(f(base))
assert np.abs(dev_int - dev_int[0:1]).max() == 0.0
codes_dev = np.rint(dev_int.astype(np.float64) / (2 * cfg_int.eb))
codes_sim = np.rint(sim_int.astype(np.float64) / (2 * cfg_int.eb))
code_gap = np.abs(codes_dev - codes_sim).max()
assert code_gap <= N, \
    f"intring allreduce: device {code_gap} codes off the sim's grid"
print(f"OK schedule-IR parity (intring device == sim, code gap {code_gap:g} <= N)")

# ---------------------------------------------------------------------------
# Communicator/Plan surface (ISSUE 3): every legacy gz_* wrapper must be
# bitwise-identical to the corresponding GZCommunicator method, the plan
# cache must hold exactly one entry per distinct core key across repeated
# jitted calls AND re-traces, and no selector/planner call may run inside
# a traced body once the plan is cached.
# ---------------------------------------------------------------------------
import repro.core.collectives as coll
import repro.core.comm as comm_api
from repro.core.comm import GZCommunicator, clear_plan_cache, plan_cache_stats

clear_plan_cache()
comm = GZCommunicator("x", config=cfg, axis_size=N)
comm_p = GZCommunicator("x", config=cfg_p, axis_size=N)  # pipelined ring

parity = [
    ("allreduce",
     lambda x: gz_allreduce(x[0], "x", cfg)[None],
     lambda x: comm.allreduce(x[0]).value[None], base),
    ("allreduce_pipelined",
     lambda x: gz_allreduce(x[0], "x", cfg_p)[None],
     lambda x: comm_p.allreduce(x[0]).value[None], base_al),
    ("reduce_scatter",
     lambda x: gz_reduce_scatter(x[0], "x", cfg)[None],
     lambda x: comm.reduce_scatter(x[0]).value[None], base),
    ("allgather",
     lambda x: gz_allgather(x[0], "x", cfg)[None],
     lambda x: comm.allgather(x[0]).value[None], chunks),
    ("scatter",
     lambda x: gz_scatter(x[0], "x", cfg)[None],
     lambda x: comm.scatter(x[0]).value[None], xin),
    ("broadcast",
     lambda x: gz_broadcast(x[0], "x", cfg)[None],
     lambda x: comm.broadcast(x[0]).value[None], xb),
    ("all_to_all",
     lambda x: gz_all_to_all(x[0], "x", cfg)[None],
     lambda x: comm.all_to_all(x[0]).value[None], x_a2a),
]
for name, legacy, method, data in parity:
    a = np.asarray(shmap(legacy, (P("x", None),), P("x", None))(data))
    b = np.asarray(shmap(method, (P("x", None),), P("x", None))(data))
    assert np.array_equal(a, b), f"wrapper != communicator: {name}"
    print(f"OK parity gz vs comm ({name})")

# Exactly one cache entry per distinct (op, nbytes, dtype, axis_size, eb):
# the wrapper and the method above shared every plan.
keys = plan_cache_stats()["keys"]
core = [k[:5] for k in keys]
assert len(core) == len(set(core)), "duplicate core plan key"
n_ar = sum(1 for k in core
           if k[:5] == ("allreduce", base.shape[1] * 4, "float32", N, 1e-4))
assert n_ar == 1, f"expected 1 allreduce plan entry for the core key, {n_ar}"

# Re-tracing (a fresh jit wrapper) must hit the cache, and once cached no
# selector/planner call may execute — patch them to explode and re-trace.
# (ISSUE 10: comm hosts the selection authority; the legacy selector
# module is a shim over it, so comm's global is the one to intercept.)
auto_cfg = GZConfig(eb=1e-4, capacity_factor=1.2, algo="auto")
f1 = shmap(lambda x: gz_allreduce(x[0], "x", auto_cfg)[None],
           (P("x", None),), P("x", None))
np.asarray(f1(base))  # resolves + caches the auto plan
misses0 = plan_cache_stats()["misses"]


def _boom(*a, **k):
    raise AssertionError("plan resolution ran inside a traced body")


orig_sel, orig_plan = comm_api.select_allreduce_plan, coll.plan_ring_pipeline_chunks
comm_api.select_allreduce_plan = _boom
coll.plan_ring_pipeline_chunks = _boom
try:
    f2 = shmap(lambda x: gz_allreduce(x[0], "x", auto_cfg)[None],
               (P("x", None),), P("x", None))  # fresh jit -> full re-trace
    np.asarray(f2(base))
finally:
    comm_api.select_allreduce_plan = orig_sel
    coll.plan_ring_pipeline_chunks = orig_plan
assert plan_cache_stats()["misses"] == misses0, "re-trace re-resolved the plan"
print("OK plan cache: one entry per key; re-trace is selector-free")

# CollectiveResult stats channel out of a shard_map body: overflow is the
# global OR, wire accounting is static and beats the uncompressed payload.
def res_body(x):
    r = comm.allreduce(x[0])
    return r.value[None], r.overflow[None]


v, o = shmap(res_body, (P("x", None),), (P("x", None), P("x")))(base)
assert not np.asarray(o).any()
plan = comm.plan("allreduce", base.shape[1])
assert plan.wire_bytes > 0 and plan.ratio > 0
print(f"OK CollectiveResult wire={plan.wire_bytes}B ratio={plan.ratio:.2f}")

# Rebinding the same axis NAME to a different size must not reuse a stale
# resolved size from the memoized one-shot communicators: the wrapper path
# already ran "x" at size N above; now run "x" at size 2 in the same
# process and demand the true 2-rank sum.  (Needs the 8-device grid.)
if N == 8:
    mesh2 = jax.make_mesh((2, 4), ("x", "y"))
    f2ax = jax.jit(shard_map(
        lambda x: gz_allreduce(x[0], "x", cfg)[None],
        mesh=mesh2, in_specs=(P(("x", "y"), None),),
        out_specs=P(("x", "y"), None),
    ))
    x8 = base  # 8 rows -> 2 "x" groups of 4 "y" rows; sum over "x" pairs
    out2 = np.asarray(f2ax(x8))
    want2 = x8.reshape(2, 4, -1).sum(axis=0)  # true sum over the "x" axis
    err2 = np.abs(out2.reshape(2, 4, -1) - want2[None]).max()
    assert err2 <= 1e-4 * 1.05 + np.abs(want2).max() * 1e-6, \
        f"stale axis-size plan reused across meshes: err {err2}"
    print("OK same axis name at a different mesh size replans correctly")

# ---------------------------------------------------------------------------
# Non-power-of-two axes (ISSUE 4): the remainder-stage redoub, generalized
# ring and virtual-pow2 trees on 3/5/6-device submeshes vs lax.psum / exact
# oracles, within the configured error bound; the plan layer's wire
# accounting must price the ceil step counts the execute layer ships.
# The check bodies are shared with the 12-rank leg (_nonpow2_checks.py).
# ---------------------------------------------------------------------------
import _nonpow2_checks as npc

# Trimmed-slab scatter on the FULL mesh (pow2 at the default N=8): the
# trimmed schedule must be bitwise-unchanged vs the padded walk and the
# simulator replay — at pow2 they are the same classic binomial tree.
npc.check_scatter_trimmed_parity(mesh, "x", N, rng)
npc.check_scatter_trimmed_parity(mesh, "x", N, rng, pipeline_chunks=2)

if N >= 6:
    d_np = 4000  # indivisible by 3/5/6: exercises the ring tail padding
    for n_sub in (3, 5, 6):
        mesh_sub = Mesh(np.array(jax.devices()[:n_sub]), ("s",))
        npc.check_allreduce_vs_psum(mesh_sub, "s", n_sub, d_np, rng)
        npc.check_plan_accounting("s", n_sub, d_np)
    for n_sub in (3, 6):
        mesh_sub = Mesh(np.array(jax.devices()[:n_sub]), ("s",))
        npc.check_scatter_broadcast(mesh_sub, "s", n_sub, d_np, rng)
        # ISSUE 5: trimmed-slab scatter bitwise == padded reference == sim
        npc.check_scatter_trimmed_parity(mesh_sub, "s", n_sub, rng)
    npc.check_scatter_trimmed_parity(
        Mesh(np.array(jax.devices()[:6]), ("s",)), "s", 6, rng,
        pipeline_chunks=2,
    )

    # Remainder-stage redoub: fused single-pass hops must stay bitwise
    # identical to the two-kernel composition (pre-fold, doubling, unfold
    # all included), and the pipelined ring must stay within budget.
    mesh6 = Mesh(np.array(jax.devices()[:6]), ("s",))
    data6 = np.cumsum(rng.normal(0, 0.01, (6, d_np)), axis=1).astype(
        np.float32
    )
    outs_fh = {}
    for fh in (True, False):
        c6 = GZConfig(eb=1e-4, algo="redoub", capacity_factor=1.2,
                      fused_hop=fh)
        f = npc._shmap(
            lambda x, c=c6: gz_allreduce(x[0], "s", c)[None],
            (P("s", None),), P("s", None), mesh6,
        )
        outs_fh[fh] = np.asarray(f(data6))
    assert np.array_equal(outs_fh[True], outs_fh[False]), \
        "remainder redoub: fused hop != two-kernel"
    print("OK nonpow2 fused_hop bitwise == two-kernel (redoub, n=6)")

    c6p = GZConfig(eb=1e-4, algo="ring", capacity_factor=1.2,
                   pipeline_chunks=2)
    f = npc._shmap(
        lambda x: gz_allreduce(x[0], "s", c6p)[None],
        (P("s", None),), P("s", None), mesh6,
    )
    out = np.asarray(f(data6))
    want6 = data6.sum(axis=0)
    err = np.abs(out - want6[None]).max()
    assert err <= 1e-4 * 1.05 + np.abs(want6).max() * 1e-6, err
    print(f"OK nonpow2 pipelined ring n=6 err={err:.2e}")

# ---------------------------------------------------------------------------
# Guard rails (ISSUE 4 satellites): bad shapes / roots / knobs fail with
# actionable ValueErrors at trace (or construction) time — never a bare
# AssertionError from the execute layer.
# ---------------------------------------------------------------------------


def _expect_value_error(fn, *fragments):
    try:
        fn()
    except ValueError as e:
        for frag in fragments:
            assert frag in str(e), (frag, str(e))
    else:
        raise AssertionError(f"expected ValueError mentioning {fragments}")


_expect_value_error(
    lambda: shmap(
        lambda x: gz_reduce_scatter(x[0][: D - 1], "x", cfg),
        (P("x", None),), P("x"),
    )(base),
    "gz_reduce_scatter", f"size {N}", "divisible",
)
_expect_value_error(
    lambda: shmap(
        lambda x: gz_scatter(x[0], "x", cfg, root=1), (P("x", None),), P("x")
    )(xin),
    "gz_scatter", "root 0",
)
_expect_value_error(
    lambda: shmap(
        lambda x: gz_broadcast(x[0], "x", cfg, root=2)[None],
        (P("x", None),), P("x", None),
    )(xb),
    "gz_broadcast", "root 0",
)
_expect_value_error(
    lambda: shmap(
        lambda x: gz_scatter(x[0][: N * D - 1], "x", cfg),
        (P("x", None),), P("x"),
    )(xin),
    "gz_scatter", "divisible",
)
_expect_value_error(lambda: GZConfig(pipeline_chunks=3), "power of two")
_expect_value_error(lambda: GZConfig(pipeline_chunks=0), "power of two")
print("OK guard rails raise actionable ValueErrors")

print("ALL OK")
