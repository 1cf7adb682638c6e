"""Compile-only checks for a described TPU v5e chip (no chip attached).

The TPU compiler is installed with jax, so the CIM crossbar kernel is
compiled here at the widths the resnet50-imagenet run feeds it, and the
jitted quantized trace step of every conv geometry the benchmark cells
run is compiled at that cell's frames per call.  That catches what
interpret mode cannot: block shapes off the TPU's (8, 128) tiling,
operands in the wrong memory space, kernels that do not fit fast
memory.  Nothing runs, so
these tests say nothing about results or times.

The kernel is interpreted whenever ``jax.default_backend()`` is not a
TPU, which here it is not; the ``tpu_backend`` fixture reports a TPU for
the duration of this module, so the kernel lowers as it does on a chip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.cnn import CNN_BENCHMARKS, ConvLayer
from repro.core.cim import DEFAULT_SPEC
from repro.core.engine import CIMEngine, PallasEngine
from repro.core.mapping import plan_network
from repro.core.network import compile_conv_layer
from repro.core.trace import TraceExecutor
from repro.kernels.cim_matmul import cim_matmul_pallas, interpret_mode

#: (rows, K, M) of cim_matmul_pallas calls in a 2-frame resnet50-imagenet
#: run at dup_cap=128: the widest chain (s3 3x3, 18 subarrays) on a
#: ragged 98-row chunk, a 1x1 stem projection chunk, a stage-2 3x3
#: chain, and one FC grid tile (2 frames x one 256x256 subarray)
WIDTHS = {
    "widest_ragged_rows": (98, 18 * 256, 512),
    "proj_1x1": (6272, 256, 64),
    "s2_3x3": (392, 9 * 256, 256),
    "fc_grid_tile": (2, 256, 256),
}

ENGINES = {"cim": CIMEngine, "pallas": PallasEngine}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tpu_backend():
    """Report a TPU backend (so the kernel is not interpreted), with the
    persistent compilation cache off and the trace caches cleared on
    both sides — nothing compiled for the described chip is reused."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        assert not interpret_mode()
        yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), tree)


@pytest.mark.parametrize("variation", [False, True],
                         ids=["plain", "variation"])
@pytest.mark.parametrize("width", list(WIDTHS))
def test_cim_kernel_compiles_for_v5e(width, variation, one_chip,
                                     tpu_backend):
    rows, k, m = WIDTHS[width]
    nk = -(-k // DEFAULT_SPEC.n_c)
    args = [jax.ShapeDtypeStruct((rows, k), jnp.int8, sharding=one_chip),
            jax.ShapeDtypeStruct((k, m), jnp.int8, sharding=one_chip)]
    if variation:
        args.append(jax.ShapeDtypeStruct((nk, 2), jnp.float32,
                                         sharding=one_chip))

    def codes(x, w, *adc_var):
        return cim_matmul_pallas(x, w, DEFAULT_SPEC, emit_codes=True,
                                 adc_var=adc_var[0] if adc_var else None)

    compiled = jax.jit(codes).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.out_info
    assert (out.shape, out.dtype) == ((rows, m), jnp.float32)


def _cell_geometries():
    """One case per distinct jitted trace step the benchmark cells run:
    (cell, engine, layer, strip, frames), at the cell's ``dup_cap`` and
    frames per call, deduplicated by schedule shape.  Width-striped
    layers contribute each strip's schedule, as ``NetworkSimulator``
    builds them."""
    cells = [  # (config, dup_cap, frames per call, engines)
        ("resnet50-imagenet", 128, 16, ("pallas", "cim")),
        ("resnet18-cifar10", 64, 4, ("pallas",)),
        ("vgg16-imagenet", 64, 16, ("pallas",)),
    ]
    cases = []
    for model, dup_cap, frames, engines in cells:
        cnn = CNN_BENCHMARKS[model]()
        seen = set()
        for layer, lp in zip(cnn.layers,
                             plan_network(cnn, dup_cap=dup_cap).layers):
            if not isinstance(layer, ConvLayer):
                continue
            sched = compile_conv_layer(layer, lp)
            strips = [st.sched for st in sched] \
                if isinstance(sched, tuple) else [sched]
            for si, sc in enumerate(strips):
                key = (sc.h, sc.w, sc.c_in, sc.c_out, sc.k, sc.stride,
                       sc.pad, sc.tail.pool_s, sc.pack, sc.c_splits)
                if key in seen:
                    continue
                seen.add(key)
                strip = si if isinstance(sched, tuple) else None
                cases += [pytest.param(
                    model, dup_cap, frames, eng, layer.name, strip,
                    id=f"{model.split('-')[0]}-{eng}-{layer.name}"
                       + ("" if strip is None else f"-strip{si}"))
                    for eng in engines]
    return cases


@pytest.mark.parametrize("model,dup_cap,frames,engine,layer_name,strip",
                         _cell_geometries())
def test_quantized_trace_step_compiles_for_v5e(model, dup_cap, frames,
                                               engine, layer_name, strip,
                                               one_chip, tpu_backend):
    """The jitted trace step of one benchmark cell's conv layer (or
    width strip), lowered from shapes for one call's frames; on the
    Pallas engine it holds the compiled kernel."""
    cnn = CNN_BENCHMARKS[model]()
    li = [layer.name for layer in cnn.layers].index(layer_name)
    layer = cnn.layers[li]
    sched = compile_conv_layer(
        layer, plan_network(cnn, dup_cap=dup_cap).layers[li])
    if strip is not None:
        sched = sched[strip].sched
    wts = np.random.default_rng(0).standard_normal(
        (layer.k, layer.k, layer.c, layer.m))
    eng = ENGINES[engine]().set_layer(layer.name, a_scale=0.05)
    ex = TraceExecutor(sched, wts, engine=eng, use_jax=True)
    step = ex._build_jax_qfn()
    stream = jax.ShapeDtypeStruct(
        (frames, sched.hp * sched.wp, sched.c_in), jnp.int8,
        sharding=one_chip)
    compiled = step.func.lower(_shapes(step.args[0], one_chip),
                               stream).compile()
    out = compiled.out_info
    assert out.shape == (frames * sched.e * sched.f, layer.m)
    assert ("tpu_custom_call" in compiled.as_text()) == (engine == "pallas")
