"""The main path's Pallas kernels compile for a TPU v5e at a real width.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses — unaligned tiles, too much VMEM.  These tests hand the kernels
shapes on a *described* v5e chip (no chip attached) and compile them with
the installed TPU compiler, at N=10 nodes and D=2^20: the round's
aggregation kernels and the qsgd decode-accumulate kernel, plus the fused
mean over a real wire payload.  Each compiled program must hold the kernel
as a Mosaic custom call.

The topology is described inside a module fixture — never at import — so
every test worker collects the same tests, and only the worker that runs
this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N, D, BUCKET = 10, 1 << 20, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # no TPU library to describe with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip can be written to the persistent
    cache but never read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_case(name):
    from repro.kernels.masked_agg import kernel as mk
    from repro.kernels.qsgd_decode.kernel import qsgd_decode_accumulate_fwd
    f32 = jnp.float32
    stack, vec, mask = (N, D), (D,), (N,)
    return {
        "masked_median": (lambda x, m: mk.masked_median_fwd(x, m),
                          [(stack, f32), (mask, f32)]),
        "masked_cc_adaptive": (
            lambda x, v, m: mk.masked_cc_iter_fwd(x, v, m),
            [(stack, f32), (vec, f32), (mask, f32)]),
        "masked_cc_fixed_tau": (
            lambda x, v, m: mk.masked_cc_iter_fwd(x, v, m, clip_tau=2.0),
            [(stack, f32), (vec, f32), (mask, f32)]),
        "masked_krum_d2": (lambda x: mk.masked_krum_d2_fwd(x),
                           [(stack, f32)]),
        "qsgd_decode_accumulate": (
            lambda c, n, w: qsgd_decode_accumulate_fwd(
                c, n, w, levels=127, bucket_size=BUCKET),
            [(stack, jnp.int8), ((N, D // BUCKET), f32), (mask, f32)]),
    }[name]


@pytest.mark.parametrize("name", [
    "masked_median", "masked_cc_adaptive", "masked_cc_fixed_tau",
    "masked_krum_d2", "qsgd_decode_accumulate"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in specs]
    assert "tpu_custom_call" in _compile_text(fn, *args)


def test_fused_mean_over_wire_payload_compiles_for_v5e(one_chip):
    """The round's own call: masked mean straight from node-batched qsgd
    wire payloads of an odd width (the bucket count padded to 8s)."""
    from repro.kernels.masked_agg.ops import masked_mean_fused
    from repro.kernels.qsgd_decode.ops import QsgdPayload, wire_encode
    size = D + 3
    pay = jax.eval_shape(
        jax.vmap(lambda k, x: wire_encode(k, x, levels=127,
                                          bucket_size=BUCKET)),
        jax.ShapeDtypeStruct((N, 2), jnp.uint32),
        jax.ShapeDtypeStruct((N, size), jnp.float32))
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def fn(codes, norms, mask):
        payload = QsgdPayload(codes, norms, levels=127, size=size,
                              bucket_size=BUCKET)
        return masked_mean_fused(payload, mask, use_kernel=True,
                                 interpret=False)

    text = _compile_text(fn, sds(pay.codes), sds(pay.norms),
                         jax.ShapeDtypeStruct((N,), jnp.bool_,
                                              sharding=one_chip))
    assert "tpu_custom_call" in text
