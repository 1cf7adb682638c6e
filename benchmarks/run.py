"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
* ``tab4_*``   — energy / CE / throughput model vs the paper's Tab. 4
* ``fig7_*``   — VGG-11 duplication/reuse tile counts (Fig. 7)
* ``fig11_*``  — normalized-CE comparison factors (Fig. 11)
* ``fig12_*``  — crossbar utilization vs array size (Fig. 12)
* ``kernel_*`` — Pallas CIM matmul vs jnp reference wall time (the
  kernel is interpreted off a TPU: there the row is correctness-path
  timing, not TPU perf)
* ``stream_*`` — measured pipelined stream computing per model: the
  steady-state initiation interval from the simulated stage timeline
  vs ``plan_network``'s analytic bound, per-frame OFMs bitwise-checked
  against both the sequential trace run and the per-cell streaming
  oracle, and a self-normalized ``per_frame_vs_seq`` ratio (batched
  stream wall time over sequential trace wall time, same frames, same
  pass) that ``--check-regress`` gates at ``STREAM_VS_SEQ_THRESHOLD``
* ``cim_*`` — quantized CIM accuracy/energy rows (vgg11, adc 8/6/4) and
  ``cim_<model>_trace`` rows timing the fused integer-native quantized
  trace path against the exact trace on every model (the embedded
  ``ratio_vs_exact`` is gated at 2x by ``--check-regress``)
* ``robust_*`` — Monte-Carlo device-variation sweeps per model (>= 20
  seeded trials of the ``VARIATION_PRESETS`` corners on the compiled
  quantized trace path): top-1 agreement statistics and the
  zero-variation bitwise check.  Accuracy rows, not wall time —
  ``--check-regress`` never speed-gates them (it only fails on a
  committed ``False`` match field, exactly like ``cim_*``)
* ``chiplet_*`` — chiplet scale-out rows (``--chiplet``): 2- and
  4-chiplet shards of the large models on the two-level
  ``ChipletFabric`` under each shipped NoI topology — per-level
  byte-hop split (intra-mesh vs interposer), the analytic II (invariant
  under sharding: blocks never span chiplets) and the energy delta vs
  the flat single mesh — plus a ``chiplet_*_degenerate`` row per model
  asserting the 1x1-chiplet fabric reproduces the flat-mesh energy
  report exactly.  Analytic match rows: ``--check-regress`` gates them
  on their embedded ``True``/``False`` match fields (exactly like
  ``cim_*``/``robust_*``), never on wall time
* ``roofline_*`` — summary of the dry-run roofline table if present
  (skipped with a note when ``results/dryrun.json`` is absent — a
  placeholder row is never written)

Run: ``PYTHONPATH=src python -m benchmarks.run``
"""
from __future__ import annotations

import json
import os
import time


def _t(fn, *args, reps=3, **kw):
    fn(*args, **kw)  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    return (time.perf_counter() - t0) / reps * 1e6, out


def _tmin(fn, *args, reps=2, **kw):
    """Best-of-``reps`` wall time in us — no implicit warmup call (the
    caller warms caches first); the min absorbs scheduler noise on the
    shared CI box the same way ``check_regress`` does."""
    best, out = float("inf"), None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return best, out


def bench_tab4():
    from repro.configs.cnn import CNN_BENCHMARKS
    from repro.core.energy import PAPER_DOMINO_ROWS, analyze

    rows = []
    for name in CNN_BENCHMARKS:
        dup_cap = 128 if name == "resnet50-imagenet" else 64
        us, rep = _t(analyze, CNN_BENCHMARKS[name](), dup_cap=dup_cap)
        paper = PAPER_DOMINO_ROWS[name]
        rows.append((f"tab4_{name}_ce", us,
                     f"CE={rep.ce_tops_per_w:.2f}TOPS/W paper={paper['ce']}"))
        rows.append((f"tab4_{name}_thru", us,
                     f"inf/s={rep.inferences_per_s:.3g} paper={paper['inf_s']:.3g}"))
        rows.append((f"tab4_{name}_energy", us,
                     f"cim_uJ={rep.e_cim*1e6:.1f} paper={paper['cim_uJ']} "
                     f"total_uJ={rep.e_total*1e6:.1f}"))
    return rows


def bench_fig7():
    from repro.configs.cnn import CNN_BENCHMARKS
    from repro.core.mapping import plan_network

    rows = []
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    for reuse, paper in ((1, 892), (4, 286)):
        us, plan = _t(plan_network, cnn, reuse=reuse)
        rows.append((f"fig7_vgg11_reuse{reuse}", us,
                     f"tiles={plan.total_tiles} paper={paper} "
                     f"II={plan.initiation_interval}"))
    return rows


def bench_fig11():
    from repro.configs.cnn import CNN_BENCHMARKS
    from repro.core.energy import BASELINE_NORM_CE, analyze

    rep = analyze(CNN_BENCHMARKS["vgg19-imagenet"]())
    rows = []
    lo, hi = 1e9, 0.0
    for name, ce in sorted(BASELINE_NORM_CE.items()):
        ratio = rep.ce_tops_per_w / ce
        if "maeri" not in name:  # the paper's 1.15-9.49x range is CIM-only;
            lo, hi = min(lo, ratio), max(hi, ratio)
        rows.append((f"fig11_vs_{name.split()[0]}", 0.0,
                     f"CE_ratio={ratio:.2f}x"))
    rows.append(("fig11_range", 0.0,
                 f"{lo:.2f}x..{hi:.2f}x paper=1.15x..9.49x (CIM archs)"))
    return rows


def bench_fig12():
    from repro.configs.cnn import CNN_BENCHMARKS
    from repro.core.mapping import plan_network

    rows = []
    us = 0.0
    for name in ("vgg11-cifar10", "vgg16-imagenet", "resnet18-cifar10",
                 "resnet50-imagenet"):
        cnn = CNN_BENCHMARKS[name]()
        utils = []
        for n in (128, 256, 512):
            us, plan = _t(plan_network, cnn, n_c=n, n_m=n)
            utils.append(f"{n}:{plan.utilization*100:.0f}%")
        rows.append((f"fig12_{name}", us, " ".join(utils)))
    return rows


def bench_kernels():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.cim import CIMSpec
    from repro.kernels.cim_matmul import cim_matmul_pallas
    from repro.kernels.ref import cim_matmul_ref

    key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    xq = jax.random.randint(k1, (128, 1024), -128, 128, dtype=jnp.int8)
    wq = jax.random.randint(k2, (1024, 256), -128, 128, dtype=jnp.int8)
    spec = CIMSpec()

    us_p, out_p = _t(lambda: jax.block_until_ready(
        cim_matmul_pallas(xq, wq, spec)))
    us_r, out_r = _t(lambda: jax.block_until_ready(
        cim_matmul_ref(xq, wq, spec)))
    exact = bool(np.array_equal(np.asarray(out_p), np.asarray(out_r)))
    return [
        ("kernel_cim_pallas_interp", us_p, f"128x1024x256 exact_vs_ref={exact}"),
        ("kernel_cim_ref_jnp", us_r, "oracle"),
    ]


def bench_simulator():
    import numpy as np

    from repro.core.schedule import compile_conv_block
    from repro.core.simulator import BlockSimulator

    h = w = 12
    c, m, k = 4, 8, 3
    rng = np.random.default_rng(0)
    ifm = rng.integers(-4, 5, (h, w, c)).astype(np.float64)
    wts = rng.integers(-4, 5, (k, k, c, m)).astype(np.float64)
    sched = compile_conv_block("bench", h, w, c, m, k, 1, 1)

    def run():
        return BlockSimulator(sched, wts, bias=np.zeros(m)).run(ifm)

    us, out = _t(run, reps=2)
    return [("sim_conv_on_the_move_12x12", us,
             f"cycles~{(h+2)*(w+2)} macs={12*12*k*k*c*m}")]


def bench_sim_batched():
    """Batched transport: one simulated pass carries B IFMs as (B, C)
    packet lanes; per-sample wall time must beat the B=1 loop."""
    import numpy as np

    from repro.core.schedule import compile_conv_block
    from repro.core.simulator import BlockSimulator

    h = w = 12
    c, m, k = 4, 8, 3
    b = 8
    rng = np.random.default_rng(0)
    ifms = rng.integers(-4, 5, (b, h, w, c)).astype(np.float64)
    wts = rng.integers(-4, 5, (k, k, c, m)).astype(np.float64)
    sched = compile_conv_block("bench", h, w, c, m, k, 1, 1)

    def run_b1():
        return BlockSimulator(sched, wts, bias=np.zeros(m)).run(ifms[0])

    def run_b8():
        return BlockSimulator(sched, wts, bias=np.zeros(m)).run(ifms)

    us1, _ = _t(run_b1, reps=2)
    us8, _ = _t(run_b8, reps=2)
    speedup = us1 / (us8 / b)
    return [
        ("sim_batched_b1", us1, f"per_sample_us={us1:.1f}"),
        ("sim_batched_b8", us8,
         f"per_sample_us={us8 / b:.1f} speedup_per_sample={speedup:.2f}x"),
    ]


def _bench_params(cnn, rng):
    import numpy as np

    from repro.configs.cnn import ConvLayer

    params = {}
    for l in cnn.layers:
        if isinstance(l, ConvLayer):
            params[l.name] = rng.integers(
                -1, 2, (l.k, l.k, l.c, l.m)).astype(np.float64)
        else:
            params[l.name] = rng.integers(
                -1, 2, (l.c_in, l.c_out)).astype(np.float64)
    return params


def bench_network_sim():
    """Whole-network simulation: VGG-11 end-to-end from instruction
    tables over the routed NoC, batched — per-cycle interpreter vs the
    trace-compiled fast path (bitwise-equal) vs its jitted flavor."""
    import numpy as np

    from repro.configs.cnn import CNN_BENCHMARKS
    from repro.core.network import NetworkSimulator

    rng = np.random.default_rng(0)
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    params = _bench_params(cnn, rng)
    b = 4
    x = rng.integers(0, 2, (b, 32, 32, 3)).astype(np.float64)

    sim = NetworkSimulator(cnn, params)
    us, res = _t(lambda: sim.run(x), reps=2)
    rows = [("network_sim_vgg11_b4", us,
             f"per_sample_us={us / b:.1f} tiles={sim.plan.total_tiles} "
             f"chain_byte_hops={res.traffic.byte_hops['chain']}")]

    tr = NetworkSimulator(cnn, params, backend="trace")
    us_t, res_t = _t(lambda: tr.run(x), reps=3)
    exact = bool(np.array_equal(res.logits, res_t.logits)
                 and res.counters == res_t.counters)
    rows.append((
        "network_sim_vgg11_b4_trace", us_t,
        f"per_sample_us={us_t / b:.1f} speedup_vs_interp={us / us_t:.1f}x "
        f"bitwise_vs_interp={exact}"))

    # the jit flavor earns its keep at serving batch sizes (float32,
    # one im2col gemm per tile group — allclose, not bitwise)
    b_j = 64
    xj = rng.integers(0, 2, (b_j, 32, 32, 3)).astype(np.float64)
    jit = NetworkSimulator(cnn, params, backend="trace", trace_jit=True)
    us_j, _ = _t(lambda: jit.run(xj), reps=2)
    rows.append((
        f"network_sim_vgg11_b{b_j}_trace_jit", us_j,
        f"per_sample_us={us_j / b_j:.1f} "
        f"speedup_vs_interp={(us / b) / (us_j / b_j):.1f}x"))
    return rows


def bench_network_sim_resnet():
    """ResNet-18 (CIFAR) end-to-end on the trace backend: residual
    shortcuts wired through the routed mesh, checked against the jax
    reference forward (interpreter equivalence is a slow test)."""
    import numpy as np

    from repro.configs.cnn import CNN_BENCHMARKS
    from repro.core.network import NetworkSimulator

    rng = np.random.default_rng(1)
    cnn = CNN_BENCHMARKS["resnet18-cifar10"]()
    params = _bench_params(cnn, rng)
    b = 4
    x = rng.integers(0, 2, (b, 32, 32, 3)).astype(np.float64)
    sim = NetworkSimulator(cnn, params, backend="trace")
    us, res = _t(lambda: sim.run(x), reps=2)

    import jax
    import jax.numpy as jnp

    from repro.models.cnn import cnn_forward

    with jax.enable_x64(True):
        p64 = {k: jnp.asarray(v, jnp.float64) for k, v in params.items()}
        ref = np.asarray(cnn_forward(p64, jnp.asarray(x, jnp.float64), cnn))
    match = bool(np.allclose(res.logits, ref, rtol=1e-9))
    return [("network_sim_resnet18", us,
             f"per_sample_us={us / b:.1f} tiles={sim.plan.total_tiles} "
             f"match_jax={match} "
             f"residual_byte_hops={res.traffic.byte_hops['residual']}")]


#: frames per model for the streaming bench — enough to cross the fill
#: transient and read a steady-state II (the recurrence reaches steady
#: state from frame 1; a few more frames make the constancy visible).
#: The acceptance target for ``per_frame_vs_seq`` is stated at T>=4, so
#: every model streams at least 4 frames here.
STREAM_FRAMES = {"cifar10": 6, "imagenet": 4}

#: committed ``stream_*`` rows must keep their self-normalized
#: ``per_frame_vs_seq`` ratio (batched stream wall time / sequential
#: trace wall time, same frames, same pass) at or below this —
#: streaming may no longer pay a per-frame penalty over the batched
#: sequential trace beyond fill/drain noise
STREAM_VS_SEQ_THRESHOLD = 1.3


def bench_network_stream():
    """Measured stream computing (paper Tab. 4 / Fig. 7): frames overlap
    across the layer pipeline, steady-state II is *measured* from the
    simulated stage timeline and cross-checked against the analytic
    slowest-stage bound.  Each row times three executors on the same
    frames in one pass — the per-cell oracle (``batched=False``, once:
    it warms every cache), the batched numerics+timing split, and the
    sequential trace run (each best-of-2 warm) — and embeds the
    self-normalized ``per_frame_vs_seq`` ratio that ``--check-regress``
    gates at ``STREAM_VS_SEQ_THRESHOLD``.  Logits are bitwise-compared
    against both references and start/finish/FIFO timing against the
    per-cell oracle.  A final ``stream_*_cimjit`` row streams the
    quantized engine with ``trace_jit`` (bitwise vs the non-jit
    quantized stream); whether jit *wins* is box-dependent, so that row
    is informational, never speed-gated."""
    import numpy as np

    from repro.configs.cnn import CNN_BENCHMARKS
    from repro.core.energy import STEP_CLOCK_HZ
    from repro.core.network import NetworkSimulator

    rows = []
    for name in CNN_BENCHMARKS:
        rng = np.random.default_rng(0)
        cnn = CNN_BENCHMARKS[name]()
        params = _bench_params(cnn, rng)
        hw = cnn.input_hw
        t_n = STREAM_FRAMES[cnn.dataset]
        frames = rng.integers(0, 2, (t_n, hw, hw, 3)).astype(np.float64)
        dup_cap = 128 if name == "resnet50-imagenet" else 64
        sim = NetworkSimulator(cnn, params, backend="trace",
                               streaming=True, dup_cap=dup_cap)
        t0 = time.perf_counter()
        cell = sim.run_stream(frames, batched=False)  # oracle + warmup
        cell_us = (time.perf_counter() - t0) * 1e6
        # alternate batched/sequential so neither side systematically
        # runs with warmer caches; min-of-2 each
        us = seq_us = float("inf")
        for _ in range(2):
            b_us, res = _tmin(sim.run_stream, frames, reps=1)
            s_us, seq = _tmin(sim.run, frames, reps=1)
            us, seq_us = min(us, b_us), min(seq_us, s_us)
        bitwise_seq = bool(res.logits.tobytes() == seq.logits.tobytes())
        bitwise_cell = bool(res.logits.tobytes() == cell.logits.tobytes())
        timing_cell = bool(
            (res.start == cell.start).all()
            and (res.finish == cell.finish).all()
            and res.residual_fifo_depth == cell.residual_fifo_depth)
        deltas = np.diff(res.finish[:, -1])
        rows.append((
            f"stream_{name}", us,
            f"measured_II={res.measured_ii} analytic_II={res.analytic_ii} "
            f"inf/s={res.inferences_per_s(STEP_CLOCK_HZ):.3g} "
            f"fill={res.fill_latency} drain={res.drain_latency} "
            f"frames={t_n} steady={bool((deltas == deltas[-1]).all())} "
            f"fifo={res.residual_fifo_depth} "
            f"per_frame_us={us / t_n:.0f} "
            f"percell_per_frame_us={cell_us / t_n:.0f} "
            f"per_frame_vs_seq={us / seq_us:.2f}x "
            f"bitwise_vs_seq={bitwise_seq} "
            f"bitwise_vs_percell={bitwise_cell} "
            f"timing_vs_percell={timing_cell}"))
    # quantized trace_jit streaming (vgg11): the integer jit flavor
    # composes with the batched numerics pass bitwise; its wall time is
    # reported against the non-jit quantized stream without a gate
    rng = np.random.default_rng(0)
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    params = _bench_params(cnn, rng)
    t_n = STREAM_FRAMES[cnn.dataset]
    frames = rng.integers(0, 2, (t_n, 32, 32, 3)).astype(np.float64)
    calib = rng.random((2, 32, 32, 3))
    cim = NetworkSimulator(cnn, params, backend="trace", streaming=True,
                           engine="cim", calib_images=calib)
    jit = NetworkSimulator(cnn, params, backend="trace", streaming=True,
                           engine="cim", calib_images=calib,
                           trace_jit=True)
    cim_us, cim_res = _t(cim.run_stream, frames, reps=2)
    jit_us, jit_res = _t(jit.run_stream, frames, reps=2)
    rows.append((
        "stream_vgg11-cifar10_cimjit", jit_us,
        f"jit_per_frame_us={jit_us / t_n:.0f} "
        f"cim_per_frame_us={cim_us / t_n:.0f} "
        f"jit_vs_cim={jit_us / cim_us:.2f}x "
        f"bitwise_jit_vs_cim="
        f"{bool(jit_res.logits.tobytes() == cim_res.logits.tobytes())}"))
    return rows


def stream_smoke(frames: int = 4, seed: int = 0) -> int:
    """Bounded CI smoke (``--stream-smoke``): stream ``frames`` frames of
    vgg11-cifar10 through the pipelined executor; non-zero exit on any
    per-frame bitwise mismatch vs the sequential trace run, on a
    measured-vs-analytic II disagreement, or on any drift between the
    batched numerics+timing split and the per-cell oracle loop
    (``batched=False``): logits, per-frame counters/traffic, the
    start/finish timeline, and the residual-FIFO depth must all be
    identical."""
    import numpy as np

    from repro.configs.cnn import CNN_BENCHMARKS
    from repro.core.network import NetworkSimulator

    rng = np.random.default_rng(seed)
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    params = _bench_params(cnn, rng)
    xs = rng.integers(0, 2, (frames, 32, 32, 3)).astype(np.float64)
    sim = NetworkSimulator(cnn, params, backend="trace", streaming=True)
    res = sim.run_stream(xs)
    seq = sim.run(xs)
    bitwise_ok = True
    for t in range(frames):
        if res.logits[t].tobytes() != seq.logits[t].tobytes():
            print(f"stream-smoke: frame {t} OFM mismatch vs sequential")
            bitwise_ok = False
    ii_ok = res.measured_ii == res.analytic_ii
    if not ii_ok:
        print(f"stream-smoke: measured II {res.measured_ii} != analytic "
              f"II {res.analytic_ii}")
    # batched-vs-per-cell differential: the two run_stream paths must be
    # indistinguishable in every observable
    cell = sim.run_stream(xs, batched=False)
    drift = []
    if res.logits.tobytes() != cell.logits.tobytes():
        drift.append("logits")
    if not ((res.start == cell.start).all()
            and (res.finish == cell.finish).all()):
        drift.append("start/finish")
    if res.residual_fifo_depth != cell.residual_fifo_depth:
        drift.append("fifo_depth")
    for t in range(frames):
        if res.frame_counters[t] != cell.frame_counters[t]:
            drift.append(f"counters[{t}]")
        bt, ot = res.frame_traffic[t], cell.frame_traffic[t]
        if (dict(bt.byte_hops) != dict(ot.byte_hops)
                or dict(bt.packets) != dict(ot.packets)
                or dict(bt.hops) != dict(ot.hops)):
            drift.append(f"traffic[{t}]")
    if drift:
        print(f"stream-smoke: batched != per-cell on {', '.join(drift)}")
    ok = bitwise_ok and ii_ok and not drift
    print(f"stream-smoke: {'ok' if ok else 'FAIL'} — {frames} frames, "
          f"II={res.measured_ii}, fill={res.fill_latency} cycles, "
          f"bitwise={bitwise_ok}, ii_match={ii_ok}, "
          f"percell_match={not drift}")
    return 0 if ok else 1


#: adc_bits sweep for the quantized-accuracy rows (the README table)
CIM_ADC_BITS = (8, 6, 4)


def bench_cim():
    """Quantized CIM inference rows (``cim_*``): vgg11-cifar10 through
    the ``CIMEngine`` at 8/6/4 ADC bits — top-1 agreement with the float
    forward, mean logit divergence, and the precision-aware energy
    breakdown (ADC share of total) — plus a ``cim_codes`` row asserting
    the CIM and Pallas engines emit identical ADC codes end-to-end.
    These rows carry *match/accuracy* results (checked in-row), not wall
    time; ``--check-regress`` ignores them like ``dse_*``."""
    import numpy as np

    import jax.numpy as jnp

    from repro.configs.cnn import CNN_BENCHMARKS
    from repro.core.cim import CIMSpec
    from repro.core.energy import analyze
    from repro.core.engine import CIMEngine, PallasEngine
    from repro.core.network import NetworkSimulator
    from repro.models.cnn import cnn_forward, init_cnn

    import jax

    rng = np.random.default_rng(0)
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    params = {k: np.asarray(v, np.float64)
              for k, v in init_cnn(jax.random.PRNGKey(0), cnn).items()}
    b = 8
    x = rng.random((b, 32, 32, 3))
    ref = np.asarray(cnn_forward(
        {k: jnp.asarray(v, jnp.float32) for k, v in params.items()},
        jnp.asarray(x, jnp.float32), cnn))

    rows = []
    pallas_checked = False
    for bits in CIM_ADC_BITS:
        spec = CIMSpec(adc_bits=bits)
        engine = CIMEngine(spec)
        t0 = time.perf_counter()
        res = NetworkSimulator(cnn, params, backend="trace", engine=engine,
                               calib_images=x).run(x)
        us = (time.perf_counter() - t0) * 1e6
        agree = float((res.logits.argmax(-1) == ref.argmax(-1)).mean())
        # relative divergence: untrained random weights leave tiny logit
        # gaps, so top-1 agreement is a weak signal — the normalized
        # logit error is the meaningful fidelity column
        rel = float(np.linalg.norm(res.logits - ref)
                    / np.linalg.norm(ref))
        erep = analyze(cnn, cim_spec=spec)
        eb = erep.breakdown()
        derived = (f"top1_agree={agree:.3f} rel_logit_err={rel:.4f} "
                   f"cim_uJ={eb['cim_uJ']:.2f} adc_uJ={eb['cim_adc_uJ']:.2f} "
                   f"adc_share={erep.adc_share:.3f} "
                   f"CE={erep.ce_tops_per_w:.2f}TOPS/W")
        if not pallas_checked:  # code-exactness once, at the paper config
            pal = PallasEngine(spec)
            pal.calib = dict(engine.calib)
            res_p = NetworkSimulator(cnn, params, backend="trace",
                                     engine=pal).run(x[:2])
            res_c = NetworkSimulator(cnn, params, backend="trace",
                                     engine=engine).run(x[:2])
            match = res_p.logits.tobytes() == res_c.logits.tobytes()
            rows.append(("cim_codes_pallas_vs_cim", 0.0,
                         f"bitwise={match}"))
            pallas_checked = True
        rows.append((f"cim_vgg11_adc{bits}", us, derived))
    return rows


#: quantized trace must stay within 2x of the exact trace per-sample —
#: the fused integer lowering's contract (checked live by ``--cim-smoke``
#: and on the committed rows by ``--check-regress``)
QUANT_TRACE_THRESHOLD = 2.0

#: timing reps for the quantized-vs-exact ratio rows (min-of-reps: the
#: CI box is a single shared core and individual passes jitter wildly)
CIM_TRACE_REPS = {"cifar10": 3, "imagenet": 2}

#: quantized-bench input scale: `_bench_params`' {-1,0,1} integer weights
#: grow activation magnitudes ~1e56 through resnet50-imagenet's depth,
#: and the engine's float32 calibration forward overflows past f32 max
#: (3.4e38) — inf activation scales then emit invalid-cast
#: RuntimeWarnings at the int8 quantization step.  Scaling the *inputs*
#: by 2^-64 (exact in f32 and f64, weights untouched — they are shared
#: with the exact bitwise benches) recentres the whole profile inside
#: f32 range: max ~4.5e36, min ~5e-20, both orders away from the edges.
CIM_BENCH_INPUT_SCALE = 2.0 ** -64


def bench_cim_trace():
    """Compiled quantized trace rows (``cim_*_trace``): every model at
    adc_bits=8 through the fused integer-native trace lowering vs the
    exact trace path on the same frames — per-sample wall time for both
    and their ratio.  The ratio is measured in one pass (same frames,
    same box, min-of-reps for both paths), so it self-normalizes away
    host noise; ``--check-regress`` gates the committed ratio at
    ``QUANT_TRACE_THRESHOLD`` instead of speed-gating the absolute time
    (which would include calibration and gate scheduler noise).
    Bitwise interp==trace==streaming equality for the quantized path is
    covered by ``--cim-smoke`` and the test suite, not re-run here."""
    import numpy as np

    from repro.configs.cnn import CNN_BENCHMARKS
    from repro.core.network import NetworkSimulator

    rows = []
    for name in CNN_BENCHMARKS:
        rng = np.random.default_rng(0)
        cnn = CNN_BENCHMARKS[name]()
        params = _bench_params(cnn, rng)
        hw = cnn.input_hw
        b = 4 if cnn.dataset == "cifar10" else 2
        reps = CIM_TRACE_REPS[cnn.dataset]
        frames = rng.random((b, hw, hw, 3)) * CIM_BENCH_INPUT_SCALE
        dup_cap = 128 if name == "resnet50-imagenet" else 64
        t0 = time.perf_counter()
        quant = NetworkSimulator(cnn, params, backend="trace", engine="cim",
                                 calib_images=frames[:1], dup_cap=dup_cap)
        quant.run(frames[:1])  # build handles / quantize weights once
        calib_s = time.perf_counter() - t0
        exact = NetworkSimulator(cnn, params, backend="trace",
                                 dup_cap=dup_cap)
        exact.run(frames[:1])
        us_q = us_e = float("inf")
        for _ in range(reps):  # interleaved: both paths see the same load
            t0 = time.perf_counter()
            quant.run(frames)
            us_q = min(us_q, (time.perf_counter() - t0) * 1e6)
            t0 = time.perf_counter()
            exact.run(frames)
            us_e = min(us_e, (time.perf_counter() - t0) * 1e6)
        ratio = us_q / us_e
        rows.append((
            f"cim_{name}_trace", us_q,
            f"per_sample_us={us_q / b:.0f} exact_per_sample_us={us_e / b:.0f} "
            f"ratio_vs_exact={ratio:.2f}x calib_s={calib_s:.1f} adc_bits=8"))
    return rows


def cim_smoke(seed: int = 0) -> int:
    """Bounded CI smoke (``--cim-smoke``): non-zero exit on any ADC-code
    mismatch between engines — (1) a conv block through the CIM vs
    Pallas engines on both backends, including the fused vs per-tile vs
    jitted trace lowerings, (2) two fixed-seed vgg11 frames through the
    pipelined CIM executor vs the sequential trace run, and interp vs
    trace on one frame — and (3) on a quantized-vs-exact trace wall-time
    ratio above ``QUANT_TRACE_THRESHOLD`` (measured min-of-reps on the
    same frames in the same pass, so host noise divides out)."""
    import numpy as np

    from repro.configs.cnn import CNN_BENCHMARKS
    from repro.core.cim import CIMSpec
    from repro.core.engine import CIMEngine, PallasEngine
    from repro.core.network import NetworkSimulator
    from repro.core.schedule import compile_conv_block
    from repro.core.simulator import BlockSimulator
    from repro.core.trace import TraceExecutor

    rng = np.random.default_rng(seed)
    ok = True
    spec = CIMSpec(adc_bits=8, gain=64.0)

    # (1) block level: cim == pallas, interp == trace, all four bitwise
    h = w = 8
    c, m, k = 4, 6, 3
    ifm = rng.standard_normal((2, h, w, c))
    wts = rng.standard_normal((k, k, c, m))
    sched = compile_conv_block("smoke", h, w, c, m, k, 1, 1)
    a_scale = float(np.abs(ifm).max()) / 127
    cim = CIMEngine(spec).set_layer("smoke", a_scale=a_scale)
    pal = PallasEngine(spec).set_layer("smoke", a_scale=a_scale)
    outs = {
        "cim/interp": BlockSimulator(sched, wts, engine=cim).run(ifm),
        "cim/trace": TraceExecutor(sched, wts, engine=cim).run(ifm),
        "cim/trace-pertile": TraceExecutor(sched, wts, engine=cim,
                                           fused=False).run(ifm),
        "cim/trace-jit": TraceExecutor(sched, wts, engine=cim,
                                       use_jax=True).run(ifm),
        "pallas/interp": BlockSimulator(sched, wts, engine=pal).run(ifm),
        "pallas/trace": TraceExecutor(sched, wts, engine=pal).run(ifm),
    }
    base = outs["cim/interp"].tobytes()
    for name, out in outs.items():
        if out.tobytes() != base:
            print(f"cim-smoke: block codes mismatch at {name}")
            ok = False

    # (2) network level: streaming == sequential, interp == trace
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    params = _bench_params(cnn, rng)
    frames = rng.integers(0, 2, (2, 32, 32, 3)).astype(np.float64)
    engine = CIMEngine(spec)
    sim = NetworkSimulator(cnn, params, backend="trace", streaming=True,
                           engine=engine)
    sres = sim.run_stream(frames)
    seq = sim.run(frames)
    if sres.logits.tobytes() != seq.logits.tobytes():
        print("cim-smoke: streaming vs sequential logits mismatch")
        ok = False
    it = NetworkSimulator(cnn, params, backend="interp",
                          engine=engine).run(frames[:1])
    if it.logits.tobytes() != seq.logits[:1].tobytes():
        print("cim-smoke: interp vs trace logits mismatch")
        ok = False

    # (3) speed contract: the fused quantized trace must stay within
    # QUANT_TRACE_THRESHOLD of the exact trace on the same frames —
    # min-of-reps on both paths in one interleaved pass so shared-box
    # noise divides out of the ratio
    exact_sim = NetworkSimulator(cnn, params, backend="trace")
    exact_sim.run(frames[:1])  # warm both before timing
    sim.run(frames[:1])
    us_q = us_e = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        sim.run(frames)
        us_q = min(us_q, time.perf_counter() - t0)
        t0 = time.perf_counter()
        exact_sim.run(frames)
        us_e = min(us_e, time.perf_counter() - t0)
    ratio = us_q / us_e
    if ratio > QUANT_TRACE_THRESHOLD:
        print(f"cim-smoke: quantized trace {ratio:.2f}x exact trace "
              f"(> {QUANT_TRACE_THRESHOLD}x)")
        ok = False

    # (4) the deep-integer bench regime must be warning-clean on the
    # quantized path: the resnet50 bench once overflowed the float32
    # calibration forward (inf activation scales -> invalid-cast
    # RuntimeWarnings at the int8 quantization).  Promote every
    # RuntimeWarning to an error around the scaled bench build + run.
    import warnings

    cnn50 = CNN_BENCHMARKS["resnet50-imagenet"]()
    rng50 = np.random.default_rng(seed)
    params50 = _bench_params(cnn50, rng50)
    frames50 = rng50.random((1, cnn50.input_hw, cnn50.input_hw, 3)) \
        * CIM_BENCH_INPUT_SCALE
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            sim50 = NetworkSimulator(cnn50, params50, backend="trace",
                                     engine="cim", calib_images=frames50,
                                     dup_cap=128)
            sim50.run(frames50)
    except RuntimeWarning as wmsg:
        print(f"cim-smoke: resnet50 quantized bench raised {wmsg!r} — "
              "the calibration overflow fix regressed")
        ok = False
    print(f"cim-smoke: {'ok' if ok else 'FAIL'} — block cim==pallas on "
          f"both backends (fused==per-tile==jit), vgg11 stream==seq and "
          f"interp==trace under engine='cim' (II={sres.measured_ii}), "
          f"quantized/exact trace ratio {ratio:.2f}x, resnet50 bench "
          "warning-clean")
    return 0 if ok else 1


#: Monte-Carlo trials per robust_* row (the acceptance floor is 20)
ROBUST_TRIALS = 20


def _robust_derived(rep) -> str:
    z = rep.zero_var_bitwise
    return (f"trials={rep.trials} nominal_top1={rep.nominal_agree:.3f} "
            f"noisy_top1_mean={rep.agree_float.mean:.3f} "
            f"std={rep.agree_float.std:.3f} "
            f"worst={rep.agree_float.worst:.3f} "
            f"vs_nominal_mean={rep.agree.mean:.3f} "
            f"zero_var_bitwise={'n/a' if z is None else z}")


def bench_robust():
    """Monte-Carlo robustness rows (``robust_*``): every model swept for
    ``ROBUST_TRIALS`` seeded draws of each device-variation corner —
    conductance noise, stuck-at cells, and ADC offset/gain error in
    isolation, then the combined "all" corner — on the compiled
    quantized trace path.  One simulator build per model (amortized
    across all four presets); only engine handles rebuild per trial.
    The first sweep per model also checks the zero-magnitude variation
    run is bitwise-equal to the nominal engine.  These are accuracy
    rows (see the module docstring): ``--check-regress`` ignores their
    wall time."""
    import jax
    import numpy as np

    from repro.configs.cnn import CNN_BENCHMARKS
    from repro.models.cnn import init_cnn
    from repro.runtime.robustness import sweep_presets

    rows = []
    for name in CNN_BENCHMARKS:
        rng = np.random.default_rng(0)
        cnn = CNN_BENCHMARKS[name]()
        params = {k: np.asarray(v, np.float64)
                  for k, v in init_cnn(jax.random.PRNGKey(0), cnn).items()}
        hw = cnn.input_hw
        b = 4 if cnn.dataset == "cifar10" else 1
        images = rng.random((b, hw, hw, 3))
        t0 = time.perf_counter()
        reps = sweep_presets(cnn, params, images, trials=ROBUST_TRIALS)
        us = (time.perf_counter() - t0) * 1e6
        for preset, rep in reps.items():
            rows.append((f"robust_{name}_{preset}",
                         us if preset == "all" else 0.0,
                         _robust_derived(rep)))
    return rows


#: committed reference for ``--fault-smoke``: the seeded 2-trial
#: "all"-corner vgg11 sweep must reproduce these numbers exactly
#: (rounded to 6 places) — any drift means the seeded variation draw or
#: the quantized trace path it perturbs changed behavior
FAULT_SMOKE_REF = {
    "nominal_agree": 0.75,
    "agree": [0.0, 0.25],
}


def fault_smoke(seed: int = 0) -> int:
    """Bounded robustness smoke (``--fault-smoke``): 2 seeded trials of
    the "all" device-variation corner on vgg11's compiled quantized
    trace path (batch 4, fixed seed).  Non-zero exit if (1) the
    zero-magnitude variation run is not bitwise-equal to the nominal
    engine, or (2) the seeded trial accuracies drift from the committed
    ``FAULT_SMOKE_REF``."""
    import jax
    import numpy as np

    from repro.configs.cnn import CNN_BENCHMARKS
    from repro.core.variation import VARIATION_PRESETS
    from repro.models.cnn import init_cnn
    from repro.runtime.robustness import monte_carlo_sweep

    rng = np.random.default_rng(seed)
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    params = {k: np.asarray(v, np.float64)
              for k, v in init_cnn(jax.random.PRNGKey(seed), cnn).items()}
    images = rng.random((4, 32, 32, 3))
    rep = monte_carlo_sweep(cnn, params, images, VARIATION_PRESETS["all"],
                            trials=2, seed0=seed)
    ok = True
    if rep.zero_var_bitwise is not True:
        print("fault-smoke: zero-magnitude variation diverged bitwise "
              "from the nominal engine")
        ok = False
    got = {"nominal_agree": round(rep.nominal_agree, 6),
           "agree": [round(a, 6) for a in rep.per_trial]}
    if got != FAULT_SMOKE_REF:
        print("fault-smoke: seeded sweep drifted from the committed "
              f"reference\n  expected {FAULT_SMOKE_REF}\n  got      {got}")
        ok = False
    print(f"fault-smoke: {'ok' if ok else 'FAIL'} — 2 seeded trials, "
          f"zero_var_bitwise={rep.zero_var_bitwise}, "
          f"nominal_top1={rep.nominal_agree:.3f}, "
          f"noisy_vs_nominal={rep.agree.mean:.3f}")
    return 0 if ok else 1


def bench_dse(budget: int = 64):  # > default space size: exhaustive sweep
    """Design-space exploration winners (``--dse``): per model, the best
    placement found at the baseline plan vs the snake baseline — CIFAR
    winners are bitwise-validated by simulation under the found
    placement.  Rows are merged into the JSON baseline and ignored by
    ``--check-regress`` (they carry search results, not wall time)."""
    from repro.configs.cnn import CNN_BENCHMARKS
    from repro.dse.report import run_dse

    rows = []
    for name in CNN_BENCHMARKS:
        t0 = time.perf_counter()
        rep = run_dse([name], budget=budget, seed=0)[0]
        us = (time.perf_counter() - t0) * 1e6
        r = rep.row()
        bitwise = {True: "True", False: "FALSE", None: "n/a"}[
            r["validated_bitwise"]]
        rows.append((
            f"dse_{name}", us,
            f"win={r['strategy'].replace(' ', ';')} "
            f"byte_hops={r['byte_hops']:.0f} "
            f"vs_snake={-r['byte_hops_saving_pct']:+.1f}% "
            f"max_link={r['max_link_bytes']:.0f} "
            f"(snake {r['max_link_bytes_snake']:.0f}) "
            f"dTOPS/W={r['tops_per_w'] - r['tops_per_w_snake']:+.3f} "
            f"bitwise={bitwise}"))
    return rows


#: models x chiplet counts for the --chiplet rows: the large models the
#: scale-out targets (ROADMAP item 5), plus resnet18 as the CIFAR-sized
#: cross-check the smoke/test suite simulates end-to-end
CHIPLET_BENCH_SHARDS = (
    ("resnet18-cifar10", (2,)),
    ("vgg19-imagenet", (2, 4)),
    ("resnet50-imagenet", (2, 4)),
)


def bench_chiplet():
    """Chiplet scale-out rows (``--chiplet``): shard each model over a
    2-/4-chiplet ``ChipletFabric`` per shipped NoI topology and report
    the per-level byte-hop split (intra-mesh classes vs the ``noi``
    interposer level), the analytic II (invariant under sharding —
    blocks never span chiplets, so the slowest stage is unchanged) and
    the energy delta vs the flat single mesh.  A ``*_degenerate`` row
    per model asserts the 1x1-chiplet fabric's energy report equals the
    flat mesh's exactly.  All rows are analytic (no cycle simulation)
    and carry ``True``/``False`` match fields: ``--check-regress``
    match-gates them, never speed-gates."""
    from repro.configs.cnn import CNN_BENCHMARKS
    from repro.core.energy import analyze_plan
    from repro.core.mapping import plan_network
    from repro.core.noc import place_network, shard_network
    from repro.core.transport import NOI

    rows = []
    for name, counts in CHIPLET_BENCH_SHARDS:
        cnn = CNN_BENCHMARKS[name]()
        dup_cap = 128 if name == "resnet50-imagenet" else 64
        plan = plan_network(cnn, dup_cap=dup_cap)
        flat_placement = place_network(plan)
        flat = analyze_plan(cnn, plan, placement=flat_placement)

        # degenerate 1x1 fabric: every energy term and per-class routed
        # byte-hop must equal the flat mesh exactly (the refactor's
        # safety invariant, checked analytically on every model here and
        # bitwise end-to-end by --chiplet-smoke / the test suite)
        us, deg = _t(lambda: analyze_plan(
            cnn, plan, placement=shard_network(plan, 1)), reps=1)
        match = (deg.breakdown() == flat.breakdown()
                 and deg.routed_byte_hops == flat.routed_byte_hops)
        rows.append((f"chiplet_{name}_degenerate", us,
                     f"fabric_1x1_equals_flat_mesh={match} "
                     f"total_uJ={flat.e_total * 1e6:.2f}"))

        for ch in counts:
            for noi in ("mesh", "floret"):
                t0 = time.perf_counter()
                placement = shard_network(plan, ch, noi=noi)
                rep = analyze_plan(cnn, plan, placement=placement)
                us = (time.perf_counter() - t0) * 1e6
                per_class = rep.routed_byte_hops
                noi_bh = per_class.get(NOI, 0)
                mesh_bh = sum(per_class.values()) - noi_bh
                delta = 100.0 * (rep.e_total - flat.e_total) / flat.e_total
                # the analytic II is invariant under sharding because
                # blocks never span chiplets: the sharded placement's
                # block spans must equal the flat mesh's exactly (the
                # same invariant NetworkSimulator enforces on injection)
                ii_match = (placement.block_start
                            == flat_placement.block_start
                            and placement.block_end
                            == flat_placement.block_end)
                rows.append((
                    f"chiplet_{name}_c{ch}_{noi}", us,
                    f"mesh_byte_hops={mesh_bh} noi_byte_hops={noi_bh} "
                    f"analytic_II={plan.initiation_interval} "
                    f"ii_invariant={ii_match} "
                    f"noi_uJ={rep.e_noi * 1e6:.3f} "
                    f"energy_vs_single_mesh={delta:+.3f}%"))
    return rows


def chiplet_smoke(seed: int = 0) -> int:
    """Bounded chiplet CI smoke (``--chiplet-smoke``): non-zero exit on
    (1) any divergence — logits, ``TrafficCounters``, energy breakdown,
    heatmap render — between the flat mesh and the degenerate
    1x1-chiplet fabric on two fixed-seed vgg11 frames, or (2) any
    per-level (intra-mesh AND noi, exact integers) sim == energy ==
    heatmap conservation mismatch on a 2-chiplet resnet18 shard."""
    import numpy as np

    from repro.configs.cnn import CNN_BENCHMARKS
    from repro.core.energy import analyze_plan, routed_byte_hops_per_class
    from repro.core.mapping import plan_network
    from repro.core.network import NetworkSimulator
    from repro.core.noc import shard_network
    from repro.core.transport import NOI
    from repro.telemetry.heatmap import check_conservation, record_run

    ok = True

    # (1) degenerate 1x1 fabric vs flat mesh: bitwise across every view
    rng = np.random.default_rng(seed)
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    params = _bench_params(cnn, rng)
    frames = rng.integers(0, 2, (2, 32, 32, 3)).astype(np.float64)
    flat_sim = NetworkSimulator(cnn, params, backend="trace")
    fab_sim = NetworkSimulator(cnn, params, backend="trace",
                               placement=shard_network(flat_sim.plan, 1))
    flat_res, flat_rec = record_run(flat_sim, frames)
    fab_res, fab_rec = record_run(fab_sim, frames)
    checks = {
        "logits": flat_res.logits.tobytes() == fab_res.logits.tobytes(),
        "counters": dict(flat_res.traffic.byte_hops)
        == dict(fab_res.traffic.byte_hops)
        and dict(flat_res.traffic.packets) == dict(fab_res.traffic.packets),
        "energy": analyze_plan(cnn, flat_sim.plan,
                               placement=flat_sim.placement).breakdown()
        == analyze_plan(cnn, fab_sim.plan,
                        placement=fab_sim.placement).breakdown(),
        "heatmap": flat_rec.heatmap().render() == fab_rec.heatmap().render()
        and flat_rec.heatmap().per_class == fab_rec.heatmap().per_class,
    }
    for what, same in checks.items():
        if not same:
            print(f"chiplet-smoke: 1x1 fabric diverged from flat mesh "
                  f"on {what}")
            ok = False

    # (2) 2-chiplet resnet18 shard: three-way per-level conservation
    rng = np.random.default_rng(seed)
    cnn18 = CNN_BENCHMARKS["resnet18-cifar10"]()
    params18 = _bench_params(cnn18, rng)
    x = rng.integers(0, 2, (1, 32, 32, 3)).astype(np.float64)
    plan18 = plan_network(cnn18, dup_cap=64)
    sim18 = NetworkSimulator(cnn18, params18, backend="trace",
                             placement=shard_network(plan18, 2))
    res18, rec18 = record_run(sim18, x)
    analytic = routed_byte_hops_per_class(cnn18, sim18.plan, sim18.placement)
    problems = check_conservation(rec18.heatmap(), res18.traffic, analytic,
                                  flows=rec18.flows.values())
    for p in problems:
        print(f"chiplet-smoke: conservation: {p}")
    noi_bh = int(res18.traffic.byte_hops.get(NOI, 0))
    if noi_bh <= 0:
        print("chiplet-smoke: 2-chiplet shard routed zero NoI traffic — "
              "the interposer level is not being exercised")
        ok = False
    ok = ok and not problems

    print(f"chiplet-smoke: {'ok' if ok else 'FAIL'} — vgg11 1x1 fabric "
          f"bitwise vs flat mesh ({', '.join(checks)}), resnet18 "
          f"2-chiplet shard sim==energy==heatmap per level "
          f"(noi={noi_bh} byte-hops, exact)")
    return 0 if ok else 1


def bench_roofline_summary():
    path = os.path.join(os.path.dirname(__file__), "..", "results",
                        "dryrun.json")
    if not os.path.exists(path):
        # no placeholder row: the dry-run artifact is optional (it takes
        # a full compile matrix to produce) — skip loudly instead
        print("# roofline_*: results/dryrun.json not found — skipping "
              "(generate with: PYTHONPATH=src python -m repro.launch.dryrun)")
        return []
    with open(path) as f:
        data = json.load(f)
    ok = [r for r in data.values() if r.get("status") == "ok"]
    fails = [r for r in data.values() if r.get("status") == "fail"]
    skips = [r for r in data.values() if r.get("status") == "skip"]
    rows = [("roofline_cells", 0.0,
             f"ok={len(ok)} fail={len(fails)} skip={len(skips)}")]
    worst = sorted(ok, key=lambda r: r.get("roofline_fraction", 1.0))[:3]
    for r in worst:
        rows.append((f"roofline_worst_{r['arch']}_{r['shape']}", 0.0,
                     f"frac={r['roofline_fraction']:.3f} "
                     f"bneck={r['bottleneck']}"))
    return rows


#: benchmark functions whose rows are wall-time sensitive — the
#: regression gate re-runs exactly these and compares per-row
SIM_BENCHES = ("bench_simulator", "bench_sim_batched", "bench_network_sim",
               "bench_network_sim_resnet")

#: >1.5x per-sample slowdown vs the committed baseline fails CI
REGRESS_THRESHOLD = 1.5


def check_regress(baseline_path: str = "BENCH_core.json",
                  threshold: float = REGRESS_THRESHOLD) -> int:
    """Re-run the ``sim_*`` / ``network_sim_*`` benchmarks and compare
    against the committed baseline JSON; returns a non-zero exit code on
    any >``threshold``x slowdown.  Newly-added rows (present fresh but
    absent from the baseline) are informational only — the gate never
    fails on them — and non-gated baseline rows (``dse_*`` search
    results, ``cim_*`` quantized-accuracy rows, ``robust_*``
    Monte-Carlo variation rows, and ``tab4_*``/``fig*`` model rows) are
    never speed-gated.  ``cim_*``, ``robust_*``, ``chiplet_*`` and
    ``stream_*`` rows
    are instead checked for *equality of match*, not speed: each row
    carries its own bitwise/agreement result — for ``robust_*`` the
    zero-variation bitwise field, for ``chiplet_*`` the
    1x1-fabric-equals-flat-mesh and block-span-invariance fields — and
    this gate fails if any committed row of these families carries a
    ``False`` match field (the live paths themselves are gated by
    ``--cim-smoke`` / ``--fault-smoke`` / ``--chiplet-smoke``); their
    wall time includes one-off calibration, Monte-Carlo trial counts
    and jit warmup (``chiplet_*`` rows are pure analytic-model time),
    so a speed ratio on them would gate noise, not code — ``chiplet_*``
    rows are match-gated, never speed-gated.
    ``cim_*_trace`` and ``stream_*`` rows additionally embed their own
    self-normalized speed ratio (both paths timed on the same frames in
    the same pass, so CI-box jitter cancels): the gate fails if any
    model's committed ``ratio_vs_exact`` exceeds
    ``QUANT_TRACE_THRESHOLD``, if any model's committed
    ``per_frame_vs_seq`` (batched stream wall time over sequential
    trace wall time — streaming used to be documented as never
    speed-gated because the per-cell loop was fill/drain-dominated;
    the batched numerics pass retires that carve-out) exceeds
    ``STREAM_VS_SEQ_THRESHOLD``, or if either row family is missing a
    model (a vanished row would silently stop covering it).  The
    ``stream_*_cimjit`` row is informational only — whether quantized
    jit streaming wins is box-dependent.

    Each bench runs twice and the per-row *minimum* is compared —
    wall-clock on a small shared CI box jitters by tens of percent, and
    the regression gate must flag code, not scheduler noise."""
    if not os.path.exists(baseline_path):
        print(f"check-regress: baseline {baseline_path} not found")
        return 2
    with open(baseline_path) as f:
        brows = json.load(f)["rows"]
    baseline = {r["name"]: r["us_per_call"] for r in brows}
    # equality-of-match check on the committed cim_* rows: a regressed
    # quantized-engine result (bitwise=False / a broken agreement field)
    # must not sit silently in the committed baseline
    bad_match = [r["name"] for r in brows
                 if r["name"].startswith(("cim_", "robust_", "chiplet_",
                                          "stream_"))
                 and "False" in r["derived"]]
    if bad_match:
        print("check-regress: FAIL — committed cim_*/robust_*/chiplet_*/"
              f"stream_* rows carry a False match field: "
              f"{', '.join(bad_match)}")
        return 1
    # cim_*_trace ratio gate: the committed quantized-vs-exact trace
    # ratio (self-normalized — both paths timed on the same frames in
    # the same pass, see bench_cim_trace) must stay within
    # QUANT_TRACE_THRESHOLD on every model, and every model must have a
    # row — a vanished row would silently stop covering that model
    import re

    from repro.configs.cnn import CNN_BENCHMARKS

    trace_rows = {r["name"]: r["derived"] for r in brows
                  if r["name"].startswith("cim_")
                  and r["name"].endswith("_trace")}
    bad_ratio = []
    for model in CNN_BENCHMARKS:
        name = f"cim_{model}_trace"
        derived = trace_rows.get(name)
        m = re.search(r"ratio_vs_exact=([\d.]+)x", derived or "")
        if derived is None or not m:
            bad_ratio.append(f"{name} missing")
        elif float(m.group(1)) > QUANT_TRACE_THRESHOLD:
            bad_ratio.append(f"{name} {m.group(1)}x")
    if bad_ratio:
        print("check-regress: FAIL — committed cim_*_trace rows exceed "
              f"the {QUANT_TRACE_THRESHOLD}x quantized-vs-exact gate or "
              f"are missing: {', '.join(bad_ratio)}")
        return 1
    # stream_* per-frame-vs-sequential gate: the committed batched
    # stream must not cost more than STREAM_VS_SEQ_THRESHOLD x the
    # sequential trace on the same frames, on any model, and every
    # model must have a row (the *_cimjit row is informational and not
    # consulted here)
    stream_rows = {r["name"]: r["derived"] for r in brows
                   if r["name"].startswith("stream_")}
    bad_stream = []
    for model in CNN_BENCHMARKS:
        name = f"stream_{model}"
        derived = stream_rows.get(name)
        m = re.search(r"per_frame_vs_seq=([\d.]+)x", derived or "")
        if derived is None or not m:
            bad_stream.append(f"{name} missing")
        elif float(m.group(1)) > STREAM_VS_SEQ_THRESHOLD:
            bad_stream.append(f"{name} {m.group(1)}x")
    if bad_stream:
        print("check-regress: FAIL — committed stream_* rows exceed the "
              f"{STREAM_VS_SEQ_THRESHOLD}x per-frame-vs-sequential gate "
              f"or are missing: {', '.join(bad_stream)}")
        return 1
    benches = [globals()[name] for name in SIM_BENCHES]
    base_derived = {r["name"]: r.get("derived", "") for r in brows}
    fresh = {}
    fresh_derived = {}
    for fn in benches:
        for _ in range(2):
            for name, us, d in fn():
                if us < fresh.get(name, float("inf")):
                    fresh[name] = us
                    fresh_derived[name] = d

    def per_sample(derived):
        m = re.search(r"per_sample_us=([\d.]+)", derived or "")
        return float(m.group(1)) if m else None

    # compact per-row delta table: committed vs measured call time,
    # per-sample time where the row reports one, ratio and gate verdict
    failures = []
    header = (f"{'row':<28} {'committed':>12} {'measured':>12} "
              f"{'per-sample':>21} {'ratio':>7}  gate")
    print(header)
    print("-" * len(header))
    for name, us in fresh.items():
        base = baseline.get(name)
        psb, psf = per_sample(base_derived.get(name)), \
            per_sample(fresh_derived.get(name))
        ps = (f"{psb / 1e3:.1f} -> {psf / 1e3:.1f}ms"
              if psb is not None and psf is not None else "-")
        if not base:
            print(f"{name:<28} {'-':>12} {us / 1e3:>10.1f}ms "
                  f"{ps:>21} {'-':>7}  new (ungated)")
            continue
        ratio = us / base
        verdict = "FAIL" if ratio > threshold else "ok"
        print(f"{name:<28} {base / 1e3:>10.1f}ms {us / 1e3:>10.1f}ms "
              f"{ps:>21} {ratio:>6.2f}x  {verdict}")
        if ratio > threshold:
            failures.append((name, ratio))
    # a gated row that vanished (renamed / bench dropped) is a failure
    # too — otherwise the gate silently stops covering it
    for name in baseline:
        if name.startswith(("sim_", "network_sim_")) and name not in fresh:
            print(f"{name:<28} {baseline[name] / 1e3:>10.1f}ms {'-':>12} "
                  f"{'-':>21} {'-':>7}  missing FAIL")
            failures.append((name, float("inf")))
    print(f"(gate: measured <= {threshold}x committed, min of 2 runs)")
    if failures:
        worst = ", ".join(f"{n} {r:.2f}x" for n, r in failures)
        print(f"check-regress: FAIL — {worst}")
        return 1
    print("check-regress: ok")
    return 0


def telemetry_smoke(trace_out=None, seed: int = 0) -> int:
    """Bounded telemetry smoke (``--telemetry-smoke``): capture a vgg11
    link heatmap and Chrome trace; non-zero exit on (1) any per-link
    conservation mismatch (heatmap sums != ``TrafficCounters`` totals
    != analytic routed byte-hops, exact integers), (2) invalid trace
    JSON (schema/monotonicity/span-nesting), or (3) any bitwise logits
    difference between a telemetry-off and a recorder-attached run.
    ``trace_out`` (``--trace-out``) writes the captured trace there —
    CI commits it as the repo's reference Perfetto artifact."""
    import numpy as np

    from repro.configs.cnn import CNN_BENCHMARKS
    from repro.core.energy import routed_byte_hops_per_class
    from repro.core.network import NetworkSimulator
    from repro.telemetry import (Profiler, check_conservation, chrome_trace,
                                 record_run, stream_timeline_events,
                                 validate_chrome_trace, write_chrome_trace)

    ok = True
    rng = np.random.default_rng(seed)
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()
    params = _bench_params(cnn, rng)
    frames = rng.random((4, 32, 32, 3))
    with Profiler() as prof:
        sim = NetworkSimulator(cnn, params, backend="trace", streaming=True)
        off = sim.run(frames)           # telemetry off: the default path
        res, rec = record_run(sim, frames)  # recorder attached
        stream = sim.run_stream(frames)

    if res.logits.tobytes() != off.logits.tobytes():
        print("telemetry-smoke: logits changed when a recorder attached")
        ok = False

    analytic = routed_byte_hops_per_class(cnn, sim.plan, sim.placement)
    problems = check_conservation(rec.heatmap(), res.traffic, analytic,
                                  flows=rec.flows.values())
    for p in problems:
        print(f"telemetry-smoke: conservation: {p}")
    ok = ok and not problems

    stage_names = [cnn.layers[st.li].name for st in sim._stages]
    events = prof.events + stream_timeline_events(stream, stage_names)
    errors = validate_chrome_trace(chrome_trace(events))
    for e in errors[:10]:
        print(f"telemetry-smoke: trace: {e}")
    ok = ok and not errors
    if trace_out and ok:
        write_chrome_trace(trace_out, events)

    totals = rec.heatmap().class_totals()
    print(f"telemetry-smoke: {'ok' if ok else 'FAIL'} — vgg11 heatmap == "
          f"counters == analytic on {sum(totals.values())} byte-hops "
          f"across {len(rec.heatmap().combined())} links, trace "
          f"{len(events)} events valid"
          + (f", wrote {trace_out}" if trace_out and ok else ""))
    return 0 if ok else 1


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", nargs="?", const="BENCH_core.json", default=None,
                    metavar="PATH",
                    help="also write the rows as JSON (default BENCH_core.json)"
                    )
    ap.add_argument("--check-regress", nargs="?", const="BENCH_core.json",
                    default=None, metavar="BASELINE",
                    help="re-run sim_*/network_sim_* rows and fail on a "
                         f">{REGRESS_THRESHOLD}x slowdown vs the committed "
                         "baseline JSON")
    ap.add_argument("--dse", action="store_true",
                    help="also run the per-model mapping DSE and emit "
                         "dse_* winner rows (merged into the JSON "
                         "baseline; without --dse a --json rewrite keeps "
                         "the previously committed dse_* rows)")
    ap.add_argument("--chiplet", action="store_true",
                    help="also emit chiplet_* scale-out rows (per-level "
                         "byte-hop split, analytic II, energy delta vs "
                         "single mesh; match-gated by --check-regress, "
                         "never speed-gated; without --chiplet a --json "
                         "rewrite keeps the committed chiplet_* rows)")
    ap.add_argument("--chiplet-smoke", action="store_true",
                    help="bounded chiplet-fabric smoke for CI: vgg11 "
                         "1x1-fabric bitwise vs the flat mesh (logits, "
                         "counters, energy, heatmap) plus a 2-chiplet "
                         "resnet18 shard's per-level three-way "
                         "conservation check; non-zero exit on mismatch")
    ap.add_argument("--stream-smoke", action="store_true",
                    help="bounded streaming smoke for CI: 4 fixed-seed "
                         "vgg11 frames through the pipelined executor; "
                         "fails on any bitwise mismatch vs the sequential "
                         "trace run, on a measured-vs-analytic II "
                         "disagreement, or on any drift (logits, "
                         "counters, timeline, FIFO depth) between the "
                         "batched path and the per-cell oracle")
    ap.add_argument("--cim-smoke", action="store_true",
                    help="bounded quantized-engine smoke for CI: a conv "
                         "block through the CIM vs Pallas engines on both "
                         "backends plus 2 fixed-seed vgg11 frames under "
                         "engine='cim'; fails on any ADC-code mismatch "
                         "between engines or executors")
    ap.add_argument("--fault-smoke", action="store_true",
                    help="bounded device-variation smoke for CI: a seeded "
                         "2-trial vgg11 Monte-Carlo sweep; fails if the "
                         "zero-variation path diverges bitwise from the "
                         "nominal engine or the seeded trial accuracies "
                         "drift from the committed reference")
    ap.add_argument("--telemetry-smoke", action="store_true",
                    help="bounded telemetry smoke for CI: vgg11 link "
                         "heatmap + Chrome trace; fails on any per-link "
                         "conservation mismatch, invalid trace JSON, or "
                         "a telemetry-off bitwise divergence")
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="write a Chrome trace (host wall-clock spans; "
                         "with --telemetry-smoke also the vgg11 stage "
                         "timeline) — open in https://ui.perfetto.dev")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.check_regress:
        raise SystemExit(check_regress(args.check_regress))
    if args.stream_smoke:
        raise SystemExit(stream_smoke())
    if args.cim_smoke:
        raise SystemExit(cim_smoke())
    if args.fault_smoke:
        raise SystemExit(fault_smoke())
    if args.telemetry_smoke:
        raise SystemExit(telemetry_smoke(args.trace_out))
    if args.chiplet_smoke:
        raise SystemExit(chiplet_smoke())

    prof = None
    if args.trace_out:
        from repro.telemetry import Profiler
        prof = Profiler().install()
    rows = []
    print("name,us_per_call,derived")
    benches = [bench_tab4, bench_fig7, bench_fig11, bench_fig12,
               bench_kernels, bench_simulator, bench_sim_batched,
               bench_network_sim, bench_network_sim_resnet,
               bench_network_stream, bench_cim, bench_cim_trace,
               bench_robust, bench_roofline_summary]
    if args.dse:
        benches.append(bench_dse)
    if args.chiplet:
        benches.append(bench_chiplet)
    failed = []
    for fn in benches:
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.1f},{derived}")
                rows.append({"name": name, "us_per_call": round(us, 2),
                             "derived": derived})
        except Exception as e:  # noqa: BLE001 - reported, then exit 1
            failed.append(fn.__name__)
            print(f"{fn.__name__},0,ERROR {type(e).__name__}: {e}")
            rows.append({"name": fn.__name__, "us_per_call": 0.0,
                         "derived": f"ERROR {type(e).__name__}: {e}"})

    if args.json:
        # a rewrite that produced no fresh dse_*/chiplet_* rows (flag
        # not passed, or the bench errored) keeps the committed rows of
        # that family instead of silently dropping them
        for prefix in ("dse_", "chiplet_"):
            if any(r["name"].startswith(prefix) for r in rows) \
                    or not os.path.exists(args.json):
                continue
            try:
                with open(args.json) as f:
                    rows.extend(r for r in json.load(f)["rows"]
                                if r["name"].startswith(prefix))
            except (KeyError, ValueError):
                pass
        with open(args.json, "w") as f:
            json.dump({"bench": "core", "rows": rows}, f, indent=1)
        print(f"# wrote {args.json} ({len(rows)} rows)")

    if prof is not None:
        from repro.telemetry import write_chrome_trace
        prof.uninstall()
        write_chrome_trace(args.trace_out, prof.events)
        print(f"# wrote {args.trace_out} ({len(prof.events)} trace events)")
    if failed:
        raise SystemExit(f"# {len(failed)} bench(es) failed: "
                         f"{', '.join(failed)}")


if __name__ == "__main__":
    main()
