"""Rooted comm facade ops (reduce/gather/scatter/monitored_barrier parity)."""

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu import comm


def _mesh(devices):
    return Mesh(np.asarray(devices[:4]), ("dp",))


def test_reduce_lands_on_dst_only(devices):
    mesh = _mesh(devices)
    x = jnp.arange(4, dtype=jnp.float32)  # shard i holds [i]

    def f(xs):
        return comm.reduce(xs, "dp", dst_index=2)

    out = shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))(x)
    np.testing.assert_array_equal(np.asarray(out), [0, 0, 6, 0])


def test_gather_concatenates_on_dst(devices):
    mesh = _mesh(devices)
    x = jnp.arange(4, dtype=jnp.float32)

    def f(xs):
        return comm.gather(xs, "dp", dst_index=1)

    out = shard_map(f, mesh=mesh, in_specs=P("dp"),
                    out_specs=P("dp"))(x)
    got = np.asarray(out).reshape(4, 4)
    np.testing.assert_array_equal(got[1], [0, 1, 2, 3])
    np.testing.assert_array_equal(got[0], np.zeros(4))


def test_scatter_distributes_src_chunks(devices):
    mesh = _mesh(devices)
    # every rank holds a full [8] array; src rank 0's is authoritative
    x = jnp.tile(jnp.arange(8, dtype=jnp.float32)[None], (4, 1))

    def f(xs):
        return comm.scatter(xs[0], "dp", src_index=0)

    out = shard_map(f, mesh=mesh, in_specs=P("dp", None),
                    out_specs=P("dp"))(x)
    np.testing.assert_array_equal(np.asarray(out), np.arange(8))


def test_monitored_barrier_returns_wait():
    dt = comm.monitored_barrier("test", timeout_s=10.0)
    assert dt >= 0.0


def test_gather_scatter_support_pytrees(devices):
    mesh = _mesh(devices)
    x = {"a": jnp.arange(4, dtype=jnp.float32),
         "b": jnp.arange(8, dtype=jnp.float32).reshape(4, 2)}

    def g(xs):
        return comm.gather(xs, "dp", dst_index=0)

    out = shard_map(g, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))(x)
    got_a = np.asarray(out["a"]).reshape(4, 4)
    np.testing.assert_array_equal(got_a[0], [0, 1, 2, 3])

    full = {"w": jnp.tile(jnp.arange(8, dtype=jnp.float32)[None], (4, 1))}

    def sc(xs):
        return comm.scatter({"w": xs["w"][0]}, "dp", src_index=0)

    out2 = shard_map(sc, mesh=mesh, in_specs=P("dp", None),
                     out_specs=P("dp"))(full)
    np.testing.assert_array_equal(np.asarray(out2["w"]), np.arange(8))
