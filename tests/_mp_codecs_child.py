"""Child: wire-codec equivalence on real multi-device shard_map runs
(ISSUE 8 acceptance).

Run in a subprocess by tests/test_collectives_multidevice.py at N=8 and
(via GZ_CHILD_DEVICES) N=6.  Proves, on actual compressed collective
executions:

  * the DEFAULT config (no codec named) is bitwise-identical — results
    AND provisioned wire bytes — to an explicit ``codec="lorenzo"``
    config: the registry changed nothing for existing callers;
  * ``codec="lorenzo+entropy"`` produces BITWISE the same allreduce
    results as ``codec="lorenzo"`` on both ring and redoub (identical
    quantization grid; the entropy stage is lossless on the codes and
    every reduce hop rounds through the same FMA kernels), and both stay
    within eb of the float64 exact sum;
  * the entropy plan provisions the SAME wire bytes as dense (shared
    capacity: the trimmed stream never exceeds the dense bitpack) while
    its TRUE payload (CollectiveResult-independent, measured via
    ``payload_bytes``) is strictly smaller on smooth data;
  * ``codec="lossless"`` and ``codec="passthrough"`` agree bitwise with
    each other (both exact, same schedule arithmetic) and match the
    uncompressed reference;
  * data movers (broadcast / scatter / allgather / all_to_all) stay
    within eb under the entropy codec;
  * a starved-capacity entropy stream still trips the overflow flag and
    ``on_overflow="fallback"`` recovers the exact psum.

Prints 'OK <name>' per check and an 'ALL OK' sentinel.
"""
from _child_env import pin_device_count

N = pin_device_count(8)

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import comm
from repro.core.collectives import GZConfig
from repro.core.shmap import shard_map

EB = 1e-4
D = 6144  # divisible by every child N (6, 8)
rng = np.random.default_rng(0)
BASE = jnp.asarray(np.cumsum(rng.normal(0, 0.01, (N, D)), axis=1),
                   jnp.float32)
EXACT = np.sum(np.asarray(BASE, np.float64), axis=0)
MESH = Mesh(np.array(jax.devices()[:N]), ("x",))


def _comm(codec="lorenzo", **cfg_kw):
    cfg = GZConfig(eb=EB, codec=codec, **cfg_kw)
    return comm.GZCommunicator("x", config=cfg, axis_size=N)


def _run(c, op, x=BASE, **kw):
    def body(v):
        r = getattr(c, op)(v[0], **kw)
        return r.value[None], r.overflow[None]

    f = jax.jit(shard_map(
        body, mesh=MESH, in_specs=(P("x", None),),
        out_specs=(P("x", None), P("x")),
    ))
    out, ovf = f(x)
    return np.asarray(out), bool(np.any(np.asarray(ovf)))


def ok(name):
    print(f"OK {name}")


# -- default config is bitwise the explicit lorenzo codec -------------------

c_default = comm.GZCommunicator(
    "x", config=GZConfig(eb=EB), axis_size=N
)
c_lorenzo = _comm("lorenzo")
out_d, _ = _run(c_default, "allreduce")
out_l, ovf_l = _run(c_lorenzo, "allreduce")
assert not ovf_l
assert np.array_equal(out_d, out_l), "default != explicit codec='lorenzo'"
pd = c_default.plan("allreduce", (D,))
pl = c_lorenzo.plan("allreduce", (D,))
assert pd is pl, "default and codec='lorenzo' must share one cache entry"
assert pd.codec == "lorenzo" and len(pd.notes) == 1  # the kernels' walk
assert pd.notes[0].startswith("codec walk: ")
ok("default-is-lorenzo")

# -- entropy == lorenzo bitwise on both allreduce algorithms ----------------

slack = max(np.abs(EXACT).max(), 1.0) * 1e-6
for algo in ("redoub", "ring"):
    out_a, ovf_a = _run(_comm("lorenzo", algo=algo), "allreduce")
    out_e, ovf_e = _run(_comm("lorenzo+entropy", algo=algo), "allreduce")
    assert not ovf_a and not ovf_e
    assert np.array_equal(out_a, out_e), (
        f"lorenzo+entropy diverged from lorenzo on {algo} "
        f"(maxdiff {np.max(np.abs(out_a - out_e))})"
    )
    err = np.max(np.abs(out_e[0].astype(np.float64) - EXACT))
    assert err <= N * EB + slack, f"{algo} entropy error {err} > bound"
    ok(f"entropy-bitwise-{algo}")

# -- shared provisioning, strictly smaller true payload ---------------------

pe = _comm("lorenzo+entropy").plan("allreduce", (D,))
assert pe.wire_bytes == pl.wire_bytes, (
    "entropy must share the dense provisioning (stream never longer)"
)
comp_l = GZConfig(eb=EB, codec="lorenzo").compressor()
comp_e = GZConfig(eb=EB, codec="lorenzo+entropy").compressor()
x0 = BASE[0]
payload_l = int(jax.device_get(comp_l.compress(x0, EB).payload_bytes()))
payload_e = int(jax.device_get(comp_e.compress(x0, EB).payload_bytes()))
assert payload_e < payload_l, (
    f"entropy payload {payload_e} not < dense {payload_l} on smooth data"
)
ok("entropy-payload-smaller")

# -- exact codecs agree with each other and the reference -------------------

out_x, ovf_x = _run(_comm("lossless"), "allreduce")
out_p, ovf_p = _run(_comm("passthrough"), "allreduce")
assert not ovf_x and not ovf_p
assert np.array_equal(out_x, out_p), "lossless != passthrough (both exact)"
err = np.max(np.abs(out_x[0].astype(np.float64) - EXACT))
assert err <= slack * N, f"exact-codec allreduce error {err}"
ok("exact-codecs-agree")

# -- data movers under the entropy codec ------------------------------------

c_e = _comm("lorenzo+entropy")

out, ovf = _run(c_e, "broadcast")
assert not ovf
assert np.max(np.abs(out - np.asarray(BASE[0])[None, :])) <= EB + slack
ok("entropy-broadcast")

out, ovf = _run(c_e, "scatter")
assert not ovf
chunk = D // N
src = np.asarray(BASE[0])
for r in range(N):
    got = out[r][:chunk]
    want = src[r * chunk:(r + 1) * chunk]
    assert np.max(np.abs(got - want)) <= EB + slack
ok("entropy-scatter")

xg = BASE[:, :2048]
out, ovf = _run(c_e, "allgather", x=xg)
assert not ovf
want = np.asarray(xg).reshape(-1)
assert np.max(np.abs(out[0][: want.size] - want)) <= EB + slack
ok("entropy-allgather")

xa = BASE[:, : (D // N) * N]
out, ovf = _run(c_e, "all_to_all", x=xa)
assert not ovf
want = np.asarray(xa).reshape(N, N, -1).transpose(1, 0, 2).reshape(N, -1)
assert np.max(np.abs(out - want)) <= EB + slack
ok("entropy-all-to-all")

# -- overflow detection + lossless fallback under entropy -------------------

rough = jnp.asarray(rng.normal(0, 100.0, (N, D)), jnp.float32)
c_starved = comm.GZCommunicator(
    "x",
    config=GZConfig(eb=1e-6, capacity_factor=0.02, codec="lorenzo+entropy",
                    on_overflow="fallback"),
    axis_size=N,
)
out, ovf = _run(c_starved, "allreduce", x=rough)
assert ovf, "starved entropy stream must flag overflow"
psum_ref = jax.jit(shard_map(
    lambda v: jax.lax.psum(v[0], "x")[None], mesh=MESH,
    in_specs=(P("x", None),), out_specs=P("x", None),
))(rough)
assert np.array_equal(out, np.asarray(psum_ref)), (
    "fallback must recover the bitwise lax.psum result"
)
ok("entropy-overflow-fallback")

print("ALL OK")
