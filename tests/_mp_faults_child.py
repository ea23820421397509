"""Child: overflow-safe degradation + deterministic fault injection
(DESIGN.md §9 acceptance).

Run in a subprocess by tests/test_collectives_multidevice.py (8 virtual
devices; the CI faults leg re-runs the whole file at N=6 via
GZ_CHILD_DEVICES).  Proves, on real multi-device shard_map executions:

  * FORCED capacity overflow (rough data x starved capacity_factor) with
    ``on_overflow="fallback"``: the in-trace lossless re-execute returns
    BITWISE the uncompressed reference for allreduce (redoub/ring/
    intring), reduce_scatter, allgather, scatter and broadcast, across
    non-power-of-two submeshes — and the overflow bit still reports the
    event;
  * ``on_overflow="flag"`` on the same inputs only flags (back-compat);
  * the two-level (node x local) hierarchical allreduce degrades to the
    same exact composite-axis psum;
  * seeded NaN/Inf input poisoning (core/faults.py) trips the distinct
    ``nonfinite`` health bit and recovers the exact psum of the
    SANITIZED inputs (bitwise vs a device psum of the numpy-twin
    poisoned arrays — faults.poison_np embeds identical constants);
  * the seeded "overflow" fault kind forces a genuine capacity overflow
    on otherwise-compressible data;
  * seeded wire bitflips are SILENT corruption with
    ``verify_streams=False`` (output differs from the clean run, no flag
    raised — the undetected-corruption hazard this leg exists to make
    fatal) and are detected + losslessly recovered with
    ``verify_streams=True`` + fallback;
  * per-communicator health counters record calls/overflow/nonfinite/
    fallbacks outside the trace;
  * dp_allreduce_grads_stats surfaces the OR-ed flags (satellite:
    the old wrapper dropped them on the scan floor);
  * a no-hypothesis shrink loop: starting from a passing
    capacity_factor, geometrically shrink until overflow fires, then
    verify the minimal failing factor still recovers exactly;
  * LAST (it poisons the runtime with an intentional raise):
    ``on_overflow="raise"`` propagates out of the jitted call.

Prints 'OK <name>' per check and an 'ALL OK' sentinel; exits via
os._exit(0) after flushing so the raise-check's dead callback tokens
cannot turn a passing run into atexit noise.
"""
from _child_env import pin_device_count

N = pin_device_count(8)

import os
import sys

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import comm, faults
from repro.core.collectives import GZConfig
from repro.core.grad_sync import SyncConfig, dp_allreduce_grads_stats
from repro.core.shmap import shard_map

rng = np.random.default_rng(0)
D = 512  # per-rank elements; multiple of every submesh size used below

# Rough high-entropy data + starved capacity: every rank's stream
# genuinely overflows the pack kernel (nothing is faked).
CFG_OVF = GZConfig(eb=1e-6, capacity_factor=0.02, on_overflow="fallback")
# Smooth compressible data + roomy capacity: never overflows.
CFG_OK = GZConfig(eb=1e-3, capacity_factor=1.2, on_overflow="fallback")

SUBMESH_NS = sorted({3, 4, N})


def submesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("x",))


def shmap(f, in_specs, out_specs, m):
    return jax.jit(shard_map(f, mesh=m, in_specs=in_specs, out_specs=out_specs))


def rough(n, d=D):
    return rng.normal(0, 100.0, (n, d)).astype(np.float32)


def smooth(n, d=D):
    return np.cumsum(rng.normal(0, 0.01, (n, d)), axis=1).astype(np.float32)


def run_allreduce(xs, n, cfg):
    def body(x):
        r = comm.GZCommunicator("x", config=cfg).allreduce(x[0])
        return r.value[None], r.overflow[None], r.nonfinite[None]

    f = shmap(body, (P("x", None),), (P("x", None), P("x"), P("x")),
              submesh(n))
    v, o, nf = f(xs)
    return np.asarray(v), np.asarray(o), np.asarray(nf)


def psum_ref(xs, n):
    f = shmap(lambda x: lax.psum(x[0], "x")[None], (P("x", None),),
              P("x", None), submesh(n))
    return np.asarray(f(xs))


# --- forced overflow -> fallback bitwise == uncompressed, all allreduce
# algorithms, non-power-of-two submeshes included ---
for n in SUBMESH_NS:
    xs = rough(n)
    ref = psum_ref(xs, n)
    for algo in ("redoub", "ring", "intring"):
        cfg = GZConfig(eb=1e-6, capacity_factor=0.02, algo=algo,
                       on_overflow="fallback")
        v, o, nf = run_allreduce(xs, n, cfg)
        assert o.all(), f"allreduce {algo} n={n}: overflow not reported"
        assert not nf.any(), f"allreduce {algo} n={n}: spurious nonfinite"
        assert np.array_equal(v, ref), \
            f"allreduce {algo} n={n}: fallback not bitwise psum"
    print(f"OK allreduce_fallback n={n} (redoub/ring/intring)")

# flag mode: same inputs only raise the bit, no lossless rerun promised
xs = rough(N)
v, o, nf = run_allreduce(
    xs, N, GZConfig(eb=1e-6, capacity_factor=0.02, on_overflow="flag"))
assert o.all() and not nf.any()
print("OK flag_mode_reports_only")

# clean data through the fallback policy: flags stay down, values are the
# ordinary compressed result (the cond must not perturb the happy path)
xs = smooth(N)
v, o, nf = run_allreduce(xs, N, CFG_OK)
assert not o.any() and not nf.any()
assert np.allclose(v[0], xs.sum(axis=0), atol=1e-1)
print("OK clean_path_unperturbed")


# --- the other collectives under forced overflow ---
def check_op_fallback(op, n):
    m = submesh(n)
    if op == "reduce_scatter":
        xs = rough(n, n * 128)  # payload must divide by the axis size

        def body(x):
            r = comm.GZCommunicator("x", config=CFG_OVF).reduce_scatter(x[0])
            return r.value[None], r.overflow[None]

        f = shmap(body, (P("x", None),), (P("x", None), P("x")), m)
        v, o = f(xs)
        ref = shmap(
            lambda x: lax.psum_scatter(
                x[0], "x", scatter_dimension=0, tiled=True)[None],
            (P("x", None),), (P("x", None)), m)(xs)
    elif op == "allgather":
        xs = rough(n, D // n)

        def body(x):
            r = comm.GZCommunicator("x", config=CFG_OVF).allgather(x[0])
            return r.value[None], r.overflow[None]

        f = shmap(body, (P("x", None),), (P("x", None), P("x")), m)
        v, o = f(xs)
        ref = shmap(lambda x: lax.all_gather(x[0], "x", tiled=True)[None],
                    (P("x", None),), (P("x", None)), m)(xs)
    elif op == "scatter":
        full = rng.normal(0, 100.0, n * D).astype(np.float32)
        xs = np.zeros((n, n * D), np.float32)
        xs[0] = full  # root-significant input

        def body(x):
            r = comm.GZCommunicator("x", config=CFG_OVF).scatter(x[0])
            return r.value[None], r.overflow[None]

        f = shmap(body, (P("x", None),), (P("x", None), P("x")), m)
        v, o = f(xs)
        ref = full.reshape(n, D)  # exact root chunks, rank r -> chunk r
    elif op == "broadcast":
        xs = np.zeros((n, D), np.float32)
        xs[0] = rng.normal(0, 100.0, D).astype(np.float32)

        def body(x):
            r = comm.GZCommunicator("x", config=CFG_OVF).broadcast(x[0])
            return r.value[None], r.overflow[None]

        f = shmap(body, (P("x", None),), (P("x", None), P("x")), m)
        v, o = f(xs)
        ref = np.tile(xs[0], (n, 1))  # exact root payload everywhere
    assert np.asarray(o).all(), f"{op} n={n}: overflow not reported"
    assert np.array_equal(np.asarray(v), np.asarray(ref)), \
        f"{op} n={n}: fallback not bitwise the lossless reference"


for op in ("reduce_scatter", "allgather", "scatter", "broadcast"):
    for n in (4, N) if N != 4 else (4,):
        check_op_fallback(op, n)
    print(f"OK {op}_fallback")

# --- hierarchical (node x local) allreduce degradation ---
if N % 2 == 0 and N >= 4:
    hmesh = Mesh(np.array(jax.devices()[:N]).reshape(2, N // 2),
                 ("node", "local"))
    xs = rough(N)

    def hbody(x):
        c = comm.GZHierCommunicator.for_axes("node", "local", config=CFG_OVF)
        r = c.allreduce(x[0, 0])
        return r.value[None, None], r.overflow[None, None]

    f = jax.jit(shard_map(hbody, mesh=hmesh,
                          in_specs=(P(("node", "local"), None),),
                          out_specs=(P(("node", "local"), None),
                                     P("node", "local"))))
    v, o = f(xs.reshape(2, N // 2, D).reshape(N, D))
    ref = xs.sum(axis=0, dtype=np.float32)
    g = jax.jit(shard_map(
        lambda x: lax.psum(x[0, 0], ("node", "local"))[None, None],
        mesh=hmesh, in_specs=(P(("node", "local"), None),),
        out_specs=P(("node", "local"), None)))
    assert np.asarray(o).all(), "hier: overflow not reported"
    assert np.array_equal(np.asarray(v), np.asarray(g(xs))), \
        "hier fallback not bitwise the composite psum"
    print("OK hier_fallback 2x%d" % (N // 2))

# --- seeded NaN / Inf input poisoning ---
for kind in ("nan", "inf"):
    spec = faults.FaultSpec(kind=kind, ranks=(1,), seed=7, n=5)
    xs = smooth(N)
    with faults.inject(spec):
        v, o, nf = run_allreduce(xs, N, CFG_OK)
    assert nf.all(), f"{kind}: nonfinite bit not set"
    assert not o.any(), f"{kind}: nonfinite misreported as overflow"
    assert np.isfinite(v).all(), f"{kind}: non-finite output escaped"
    twins = np.stack([faults.poison_np(xs[r], r, spec) for r in range(N)])
    san = np.where(np.isfinite(twins), twins, 0.0).astype(np.float32)
    assert np.array_equal(v, psum_ref(san, N)), \
        f"{kind}: recovery not bitwise psum of sanitized twins"
    print(f"OK poison_{kind}_recovered")

# the "overflow" fault kind: compressible data and a capacity that fits
# it with headroom — only the injected incompressible noise (32-bit
# codes > 0.8x capacity) can overflow, and it must
spec = faults.FaultSpec(kind="overflow", ranks=(0, 2), seed=11)
cfg_noise = GZConfig(eb=1e-3, capacity_factor=0.8, on_overflow="fallback")
xs = smooth(N)
v_clean, o_clean, _ = run_allreduce(xs, N, cfg_noise)
assert not o_clean.any()
with faults.inject(spec):
    v, o, nf = run_allreduce(xs, N, cfg_noise)
assert o.all(), "overflow fault kind did not trip the capacity check"
twins = np.stack([faults.poison_np(xs[r], r, spec) for r in range(N)])
assert np.array_equal(v, psum_ref(twins, N)), \
    "overflow-fault fallback not bitwise psum of the poisoned inputs"
print("OK fault_kind_overflow")

# --- wire bitflips: silent without verify_streams, caught with it ---
xs = smooth(N)
clean, _, _ = run_allreduce(xs, N, GZConfig(eb=1e-3, capacity_factor=0.6))
corrupting_seed = None
for seed in range(24):
    spec = faults.FaultSpec(kind="bitflip", ranks=(1,), seed=seed, n=16)
    with faults.inject(spec):
        v, o, nf = run_allreduce(
            xs, N, GZConfig(eb=1e-3, capacity_factor=0.6))
    if not np.array_equal(v, clean):
        assert not o.any() and not nf.any(), \
            "bitflip raised a flag without verify_streams (seed %d)" % seed
        corrupting_seed = seed
        break
assert corrupting_seed is not None, \
    "no bitflip seed corrupted the wire — injector is not reaching streams"
print(f"OK bitflip_silent_without_verify (seed={corrupting_seed})")

spec = faults.FaultSpec(kind="bitflip", ranks=(1,), seed=corrupting_seed,
                        n=16)
with faults.inject(spec):
    v, o, nf = run_allreduce(
        xs, N,
        GZConfig(eb=1e-3, capacity_factor=0.6, verify_streams=True,
                 on_overflow="fallback"))
assert np.asarray(o).all(), "verify_streams did not detect the bitflip"
assert np.array_equal(v, psum_ref(xs, N)), \
    "bitflip fallback not bitwise the clean psum"
print("OK bitflip_detected_and_recovered")

# --- round-targeted bitflips (ISSUE 10): a FaultSpec aimed at schedule
# round k corrupts the bit-identical wire hop in the table replay and on
# the real mesh — the detection bit of sim_allreduce_guarded must equal
# the device's, both for rounds inside the table and for rounds past its
# end (which can never match an exchange). ---
from repro.core import schedule, simulator

cfg_rt = GZConfig(eb=1e-3, capacity_factor=0.6, algo="redoub",
                  verify_streams=True, on_overflow="fallback")
sched_rt = schedule.build("allreduce", "redoub", N)
for rounds in ((1,), (0, sched_rt.n_rounds - 1), (sched_rt.n_rounds + 7,)):
    spec = faults.FaultSpec(kind="bitflip", ranks=(1,), seed=corrupting_seed,
                            n=16, rounds=rounds)
    with faults.inject(spec):
        v, o, nf = run_allreduce(xs, N, cfg_rt)
    dev_bit = bool(np.asarray(o).any())
    _, fl = simulator.sim_allreduce_guarded(list(xs), cfg_rt, algo="redoub",
                                            spec=spec)
    assert dev_bit == fl["overflow"] == fl["fallback"], \
        f"rounds={rounds}: device detection {dev_bit} != sim flags {fl}"
    if dev_bit:
        assert np.array_equal(v, psum_ref(xs, N)), \
            f"rounds={rounds}: detected but not losslessly recovered"
    print(f"OK bitflip_round_targeted rounds={rounds} detected={dev_bit}")

# --- health counters (outside-trace observability) ---
comm.clear_plan_cache()
comm.clear_health_stats()
comm.enable_health_tracking(True)
run_allreduce(rough(N), N, CFG_OVF)
run_allreduce(smooth(N), N, CFG_OK)
jax.effects_barrier()
stats = comm.health_stats()
key = ("allreduce", "'x'")
assert stats[key]["calls"] == 2, stats
assert stats[key]["overflow"] == 1, stats
assert stats[key]["fallbacks"] == 1, stats
assert stats[key]["nonfinite"] == 0, stats
comm.enable_health_tracking(False)
print("OK health_counters")

# --- grad_sync surfaces the OR-ed flags (satellite) ---
mesh = submesh(N)
sync = SyncConfig(gz=GZConfig(eb=1e-6, capacity_factor=0.02,
                              on_overflow="fallback"))
grads = {"w": rough(N, 64).reshape(N, 8, 8), "b": rough(N, 8)}


def gbody(g):
    g = jax.tree.map(lambda a: a[0], g)
    out, st = dp_allreduce_grads_stats(g, ("x",), sync)
    return (jax.tree.map(lambda a: a[None], out),
            st.overflow[None], st.nonfinite[None])


f = jax.jit(shard_map(
    gbody, mesh=mesh,
    in_specs=({"w": P("x", None, None), "b": P("x", None)},),
    out_specs=({"w": P("x", None, None), "b": P("x", None)},
               P("x"), P("x"))))
out, o, nf = f(grads)
assert np.asarray(o).all(), "grad sync dropped the overflow flag"
ww = np.asarray(out["w"])[0]
# fallback + relative_eb: sum is exact up to the scale fold (f32 mul/div)
assert np.allclose(ww, grads["w"].sum(axis=0), rtol=1e-5), \
    "grad fallback values wrong"
print("OK grad_sync_stats")

# --- shrink loop: geometrically shrink capacity_factor to the minimal
# failing value, then verify exact recovery right at the boundary ---
xs = smooth(4)
factor, failing = 1.2, None
while factor > 1e-3:
    cfg = GZConfig(eb=1e-5, capacity_factor=factor, on_overflow="fallback")
    v, o, nf = run_allreduce(xs, 4, cfg)
    if o.any():
        failing = factor
        assert np.array_equal(v, psum_ref(xs, 4)), \
            f"shrunk factor {factor}: fallback not bitwise psum"
        break
    factor /= 2.0
assert failing is not None, "no capacity_factor small enough to overflow"
print(f"OK capacity_shrink_property (first failing factor={failing:g})")

# --- raise policy LAST: the io_callback raise propagates, and the
# dead runtime tokens it leaves must not poison the exit path ---
raised = False
try:
    run_allreduce(rough(N), N,
                  GZConfig(eb=1e-6, capacity_factor=0.02,
                           on_overflow="raise"))
    jax.effects_barrier()
except Exception as e:  # XlaRuntimeError wrapping the RuntimeError
    raised = "degraded" in str(e) or "overflow" in str(e)
assert raised, "on_overflow='raise' did not propagate"
print("OK raise_policy")

print("ALL OK")
sys.stdout.flush()
os._exit(0)
