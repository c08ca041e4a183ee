"""Ahead-of-time compiles of the Pallas overrides for a described TPU v5e.

The CPU suite runs the kernels in the Pallas interpreter, which accepts
what the chip's compiler refuses (a traced value captured by the kernel,
a bf16 compare, a tile over the scoped-VMEM limit). These cases hand the
real kernel, ``interpret=False``, at the shapes the chip smoke runs, to
the TPU compiler installed here — a compile, not a run.

The conv path's fused BatchNorm+activation epilogue is no kernel: the
last cases compile conv -> ``fused_bn_act`` -> conv at the benchmark
cells' shapes and hold the compiler to what PR 27 took the Pallas
epilogue out for — the FMA+select rides inside the neighbouring
convolutions' fusions, with no ``custom-call`` and no copy or reshape of
the activation map between them.

The topology is described inside a fixture, in the test's own process,
and in this one file: only one process may load the TPU library (see the
on-chip-measurement guide, section 2).
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip can be written to JAX's
    # persistent cache but not read back without one
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


# each builder: (function, [(shape, dtype) of each argument], whether the
# kernel's own gate admits the shape — a refused shape would compile the
# generic lowering and prove nothing)
def _layer_norm(shape, dtype):
    ln = pk.make_layer_norm_override(interpret=False)
    d = shape[-1]
    return (lambda x, g, b: ln(x, g, b, eps=1e-5),
            [(shape, dtype), ((d,), jnp.float32), ((d,), jnp.float32)],
            pk.supported(jax.ShapeDtypeStruct(shape, dtype)))


def _softmax(shape, dtype):
    return (pk.make_softmax_override(interpret=False), [(shape, dtype)],
            pk.supported(jax.ShapeDtypeStruct(shape, dtype)))


def _flash(shape, dtype):
    q = jax.ShapeDtypeStruct(shape, dtype)
    return (pk.make_flash_attention_override(interpret=False),
            [(shape, dtype)] * 3, pk.flash_supported(q, q, 256, 256))


def _flash_grad(shape, dtype):
    fa, args, admitted = _flash(shape, dtype)
    return (jax.grad(lambda q, k, v: jnp.sum(fa(q, k, v).astype(jnp.float32)),
                     argnums=(0, 1, 2)), args, admitted)


_BUILDERS = {"layer_norm": _layer_norm, "softmax": _softmax,
             "flash_fwd": _flash, "flash_grad": _flash_grad}

# kernel, shape, dtype — the chip smoke's kernel-phase shapes first, then
# every shape the chip's compiler refused before PR 22
_CASES = [
    ("layer_norm", (4096, 768), "bfloat16"),
    ("layer_norm", (4096, 768), "float32"),
    ("layer_norm", (8, 768), "bfloat16"),
    ("softmax", (4096, 1024), "float32"),
    ("softmax", (4096, 1024), "bfloat16"),
    ("flash_fwd", (32, 128, 12, 64), "bfloat16"),
    ("flash_grad", (32, 128, 12, 64), "bfloat16"),
    # the widest the gates admit, in f32: the row block shrinks to fit the
    # 16 MiB scoped VMEM limit (256 rows were 16.03 MiB)
    ("layer_norm", (4096, 4096), "float32"),
    ("softmax", (4096, 4096), "float32"),
]


@pytest.mark.parametrize(
    "kernel,shape,dtype", _CASES,
    ids=[f"{k}-{'x'.join(map(str, s))}-{d}" for k, s, d in _CASES])
def test_kernel_compiles_for_v5e(one_chip, kernel, shape, dtype):
    fn, args, admitted = _BUILDERS[kernel](shape, jnp.dtype(dtype))
    assert admitted
    specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ------------------------------------------------- the conv path's epilogue
# activation shape between the two convolutions, epilogue slope: the
# 128-multiple channel widths of resnet50-fit-b256 (relu) and
# tinyyolo-fit-b256 (leaky) that the deleted kernel's gate admitted
_EPILOGUE_CASES = [
    ((256, 28, 28, 128), 0.0),
    ((256, 14, 14, 256), 0.0),
    ((256, 7, 7, 512), 0.0),
    ((256, 52, 52, 128), 0.01),
    ((256, 26, 26, 256), 0.01),
    ((256, 13, 13, 1024), 0.01),
]


def _conv_bn_act_conv(alpha):
    """1x1 conv -> fused BN+activation (train mode) -> 1x1 conv in NHWC,
    forward and backward, as a train step runs a block's inside."""
    from deeplearning4j_tpu.nn.layers import BatchNormalization, fused_bn_act
    from deeplearning4j_tpu.ops import convolution as conv_ops
    bn = BatchNormalization()
    bn.data_format = "NHWC"

    def forward(x, w1, gamma, beta, w2):
        y = conv_ops.conv2d(x, w1, data_format="NHWC")
        state = {"mean": jnp.zeros_like(gamma), "var": jnp.ones_like(gamma)}
        y, _ = fused_bn_act(bn, {"gamma": gamma, "beta": beta}, state, y,
                            True, alpha)
        return conv_ops.conv2d(y, w2, data_format="NHWC")

    return jax.grad(lambda *a: jnp.sum(forward(*a).astype(jnp.float32)),
                    argnums=(0, 1, 2, 3, 4))


@pytest.mark.parametrize(
    "shape,alpha", _EPILOGUE_CASES,
    ids=[f"{'x'.join(map(str, s))}-{'leaky' if a else 'relu'}"
         for s, a in _EPILOGUE_CASES])
def test_fused_epilogue_rides_in_the_conv_fusions(one_chip, shape, alpha):
    pk.install_platform_overrides()     # as the benchmark's driver does
    try:
        c = shape[-1]
        args = [(shape, jnp.bfloat16), ((c, c, 1, 1), jnp.bfloat16),
                ((c,), jnp.float32), ((c,), jnp.float32),
                ((c, c, 1, 1), jnp.bfloat16)]
        specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                 for s, d in args]
        text = jax.jit(_conv_bn_act_conv(alpha)).lower(*specs).compile() \
            .as_text()
    finally:
        pk.uninstall_platform_overrides()
    assert "custom-call(" not in text
    # the scheduled program: what runs between the convolution fusions
    entry = text[text.index("\nENTRY "):]
    activation = "bf16[" + ",".join(map(str, shape)) + "]"
    moved = re.findall(
        r"= %s\S* (?:copy|reshape|transpose)\(" % re.escape(activation), entry)
    assert not moved, moved


# ------------------------------------------------ the causal attention core
def test_causal_attention_compiles_for_v5e_without_a_kernel(one_chip):
    """The looped language model's attention core at the benchmark cell's
    shape, forward and backward: plain matmuls in query blocks, no
    ``custom-call`` of the program's (the chip probes of PR 28 and PR 30
    chose it), no [T, T] tensor for any head (the widest score block is
    512 query rows by 4,096 keys of four heads), and a backward of its own
    that runs the forward's scores again from the row log-sum-exp instead
    of rematerialising the call."""
    from deeplearning4j_tpu.ops import attention as attention_ops
    shape = (1, 4096, 16, 128)
    assert attention_ops._causal_plan(*shape[:3]) == (512, 4)
    specs = [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)] * 3
    grad = jax.grad(lambda q, k, v: jnp.sum(attention_ops.causal_attention(
        q, k, v).astype(jnp.float32)), argnums=(0, 1, 2))
    text = jax.jit(grad).lower(*specs).compile().as_text()
    assert "tpu_custom_call" not in text
    assert not re.search(r"4096,4096\]", text)
    assert "rematted_computation" not in text
    # what the head groups and the barriers between blocks buy: every
    # float32 [q, k] tensor that passes between two fusions stays on chip
    # (memory space S(1)); none is written to HBM
    entry = text[text.index("\nENTRY "):]
    scores = re.findall(r"= f32\[4,512,\d+\]\{([^}]*)\}", entry)
    assert scores and all("S(1)" in layout for layout in scores), \
        [layout for layout in scores if "S(1)" not in layout][:3]
