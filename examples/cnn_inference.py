"""VGG-11 through the full Domino pipeline: mapping plan -> schedule
tables -> NoC placement -> CIM-quantized inference -> Tab. 4 energy row.

    PYTHONPATH=src python examples/cnn_inference.py
    PYTHONPATH=src python examples/cnn_inference.py --placement hilbert
    PYTHONPATH=src python examples/cnn_inference.py --streaming
    PYTHONPATH=src python examples/cnn_inference.py --engine cim

``--placement`` swaps the snake baseline for a DSE strategy and shows
the routed-traffic delta of the optimized mapping end-to-end (the
simulated logits stay bitwise-identical — placement never changes math).
``--streaming`` runs the paper's stream computing: frames overlap across
the layer pipeline and the steady-state initiation interval is measured
from the simulated stage timeline (it must equal the analytic Tab. 4
bound, and per-frame logits stay bitwise-equal to the sequential run);
``--batch-window N`` caps the serve front-end's micro-batching admission
window (frames per batched numerics sweep — wall time only, never the
reported cycles).
``--engine`` selects the PE numerics for the whole-network simulation
(``core/engine.py``): ``exact`` float64 (default), ``cim`` w8a8 +
per-subarray ADC, or ``pallas`` (the same numerics through the Pallas
kernel, ADC-code-exact vs ``cim``) — printing the per-class logit
divergence vs the exact run, the per-sample wall time of the compiled
integer-native trace path vs the exact trace, and the ADC share of the
precision-aware energy total.  Quantized engines run the fused trace
lowering by default (``core/trace.py``): batched int8 gemms + one
vectorized ADC conversion per layer, bitwise-equal to the per-tile
interpreter.
``--variation`` (with a quantized ``--engine``) injects a named
device-variation corner (``core/variation.py`` presets: ``noise`` /
``stuck`` / ``adc`` / ``all``) and runs a seeded Monte-Carlo sweep of
``--trials`` draws through the compiled quantized trace path, printing
nominal vs noisy top-1 agreement and the zero-variation bitwise check.
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.cnn import CNN_BENCHMARKS
from repro.core.cim import CIMSpec
from repro.core.energy import analyze_plan
from repro.core.mapping import plan_network
from repro.core.noc import place_network
from repro.core.schedule import compile_conv_block
from repro.models.cnn import cnn_forward, init_cnn


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--placement", default=None,
                    choices=("snake", "boustrophedon", "hilbert", "greedy"),
                    help="run the whole-network simulation under this DSE "
                         "placement strategy and compare routed traffic "
                         "against the snake baseline")
    ap.add_argument("--streaming", action="store_true",
                    help="stream frames through the pipelined executor and "
                         "report the measured steady-state initiation "
                         "interval / fill latency / inf/s")
    ap.add_argument("--batch-window", type=int, default=None, metavar="N",
                    help="micro-batching admission window for the "
                         "closed-loop serve front-end (with --streaming): "
                         "queued requests execute as one numerics batch "
                         "of up to N frames; timing and logits are "
                         "bitwise-unchanged — only wall time moves")
    ap.add_argument("--engine", default="exact",
                    choices=("exact", "cim", "pallas"),
                    help="PE numerics engine for the whole-network "
                         "simulation: exact float64, CIM w8a8+ADC, or the "
                         "Pallas kernel flavor (ADC-code-exact vs cim)")
    ap.add_argument("--variation", default=None,
                    choices=("noise", "stuck", "adc", "all"),
                    help="device-variation preset for a seeded Monte-Carlo "
                         "robustness sweep (quantized engines only; "
                         "implies --engine cim if --engine is exact)")
    ap.add_argument("--trials", type=int, default=5,
                    help="Monte-Carlo draws for --variation")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace (host spans: calibration / "
                         "trace lowering / jit; plus the stage x frame "
                         "pipeline timeline when --streaming) — open in "
                         "https://ui.perfetto.dev")
    args = ap.parse_args()
    if args.variation and args.engine == "exact":
        args.engine = "cim"
    prof = None
    timeline_events = []
    if args.trace_out:
        from repro.telemetry.spans import Profiler

        prof = Profiler()
        prof.install()
    cnn = CNN_BENCHMARKS["vgg11-cifar10"]()

    # 1) map the network onto tiles (Fig. 7 machinery)
    plan = plan_network(cnn, reuse=4)
    placement = place_network(plan)
    print(f"{cnn.name}: {plan.total_tiles} tiles on a "
          f"{placement.noc.rows}x{placement.noc.cols} NoC, "
          f"utilization {plan.utilization*100:.1f}%, "
          f"II {plan.initiation_interval} cycles")

    # 2) compile one layer's schedule tables (the 16-bit ISA)
    layer = cnn.conv_layers[2]
    sched = compile_conv_block("L3", h=8, w=8, c_in=layer.c, c_out=layer.m,
                               k=layer.k, stride=layer.s, pad=layer.p)
    from repro.core.instructions import Instruction
    print(f"layer {layer.name}: period {sched.period} instructions/tile, "
          f"{len(sched.tiles)} tiles; tile0 table:")
    for w_ in sched.tiles[4].table[:6]:
        print("   ", Instruction.decode(w_))

    # 3) energy / throughput report (Tab. 4 row)
    rep = analyze_plan(cnn, plan)
    b = rep.breakdown()
    print(f"energy/inference: cim={b['cim_uJ']:.2f}uJ "
          f"move={b['moving_uJ']:.2f}uJ mem={b['memory_uJ']:.2f}uJ "
          f"other={b['other_uJ']:.2f}uJ offchip={b['offchip_uJ']:.1f}uJ")
    print(f"CE={rep.ce_tops_per_w:.2f} TOPS/W  "
          f"throughput={rep.throughput_tops:.1f} TOPS "
          f"({rep.inferences_per_s:.3g} inf/s)")

    # 4) run actual inference dense vs CIM-quantized (accuracy-drop demo)
    key = jax.random.PRNGKey(0)
    params = init_cnn(key, cnn)
    x = jax.random.normal(key, (4, 32, 32, 3))
    dense = cnn_forward(params, x, cnn)
    cim = cnn_forward(params, x, cnn, cim=CIMSpec(gain=64.0))
    agree = float(jnp.mean((jnp.argmax(dense, -1) == jnp.argmax(cim, -1))
                           .astype(jnp.float32)))
    corr = np.corrcoef(np.asarray(dense).ravel(), np.asarray(cim).ravel())[0, 1]
    print(f"dense vs 8-bit CIM: logits corr={corr:.4f}, "
          f"top-1 agreement={agree*100:.0f}%")

    # 5) whole-network simulation: the full VGG-11 executes from
    # compiled 16-bit instruction tables over the routed NoC, batched —
    # on the trace-compiled fast path (bitwise-equal to the per-cycle
    # interpreter; pass backend="interp" to watch the oracle instead)
    from repro.core.network import NetworkSimulator

    rng = np.random.default_rng(0)
    int_params = {
        k: rng.integers(-1, 2, np.asarray(v).shape).astype(np.float64)
        for k, v in params.items()
    }
    import time

    xb = rng.integers(0, 2, (4, 32, 32, 3)).astype(np.float64)
    exact_sim = NetworkSimulator(cnn, int_params, backend="trace")
    t0 = time.perf_counter()
    res = exact_sim.run(xb)
    exact_us = (time.perf_counter() - t0) * 1e6 / len(xb)
    ref = np.asarray(cnn_forward(
        {k: jnp.asarray(v, jnp.float32) for k, v in int_params.items()},
        jnp.asarray(xb, jnp.float32), cnn))
    print(f"whole-network sim (B=4): logits {res.logits.shape}, "
          f"top-1 match vs jax: "
          f"{(res.logits.argmax(-1) == ref.argmax(-1)).mean()*100:.0f}%")
    print("routed traffic (byte-hops): " + ", ".join(
        f"{k}={v}" for k, v in sorted(res.traffic.byte_hops.items())))

    # 5b) optional: the same network under a quantized PE engine — w8a8
    # weights resident in the crossbars, per-subarray ADC, digitally
    # accumulated codes; per-class logit divergence vs the exact run and
    # the ADC conversions' share of the precision-aware energy total
    if args.engine != "exact":
        from repro.core.energy import analyze

        qsim = NetworkSimulator(cnn, int_params, backend="trace",
                                engine=args.engine)
        qsim.run(xb[:1])  # warm: quantize weights / build handles once
        t0 = time.perf_counter()
        qres = qsim.run(xb)
        quant_us = (time.perf_counter() - t0) * 1e6 / len(xb)
        spec = qsim.pe_engine.spec
        scale = np.abs(res.logits).mean()
        per_class = np.abs(qres.logits - res.logits).mean(axis=0) / scale
        agree = (qres.logits.argmax(-1) == res.logits.argmax(-1)).mean()
        print(f"engine={args.engine} (w{spec.w_bits}a{spec.a_bits}, "
              f"{spec.adc_bits}b ADC): top-1 agreement vs exact "
              f"{agree*100:.0f}%, per-class relative logit divergence: "
              + " ".join(f"{d:.4f}" for d in per_class))
        print(f"compiled quantized trace: {quant_us:.0f} us/sample "
              f"(exact trace {exact_us:.0f} us/sample, "
              f"ratio {quant_us / exact_us:.2f}x)")
        qrep = analyze(cnn, cim_spec=spec)
        qb = qrep.breakdown()
        print(f"precision-aware energy: array={qb['cim_array_uJ']:.2f}uJ "
              f"input={qb['cim_input_uJ']:.2f}uJ "
              f"adc={qb['cim_adc_uJ']:.2f}uJ "
              f"(ADC share of total: {qrep.adc_share*100:.1f}%, "
              f"quantized CE={qrep.ce_tops_per_w:.2f} TOPS/W)")

        # 5c) optional: seeded Monte-Carlo device-variation sweep on the
        # compiled quantized trace path — conductance noise, stuck-at
        # cells and ADC offset/gain error injected behind the engine
        # seam; one simulator build, per-trial handle rebuilds only
        if args.variation:
            from repro.core.variation import VARIATION_PRESETS
            from repro.runtime.robustness import monte_carlo_sweep

            vm = VARIATION_PRESETS[args.variation]
            rrep = monte_carlo_sweep(
                cnn, int_params, xb, vm, trials=args.trials,
                engine=args.engine, seed0=0)
            print(f"variation={args.variation} ({vm.describe()}), "
                  f"{args.trials} seeded trials: noisy top-1 vs nominal "
                  f"{rrep.agree.mean*100:.0f}% (worst "
                  f"{rrep.agree.worst*100:.0f}%, std {rrep.agree.std:.3f}); "
                  f"vs float {rrep.agree_float.mean*100:.0f}% "
                  f"(nominal {rrep.nominal_agree*100:.0f}%); "
                  f"zero-variation bitwise-equal: {rrep.zero_var_bitwise}")

    # 6) optional: pipelined stream computing — successive frames overlap
    # across the layer pipeline, so throughput is set by the slowest
    # stage's initiation interval (measured here from the simulated stage
    # timeline), not by the end-to-end latency
    if args.streaming:
        from repro.core.energy import STEP_CLOCK_HZ
        from repro.runtime.serve_loop import serve_stream

        frames = rng.integers(0, 2, (6, 32, 32, 3)).astype(np.float64)
        stream_sim = NetworkSimulator(cnn, int_params, backend="trace",
                                      streaming=True)
        sres = stream_sim.run_stream(frames)
        seq = stream_sim.run(frames)
        assert sres.logits.tobytes() == seq.logits.tobytes(), \
            "streaming changed the math?!"
        print(f"streaming ({len(frames)} frames): measured II "
              f"{sres.measured_ii} cycles (analytic {sres.analytic_ii}), "
              f"fill {sres.fill_latency} cycles, "
              f"{sres.inferences_per_s(STEP_CLOCK_HZ):.3g} inf/s at "
              f"{STEP_CLOCK_HZ/1e6:.0f} MHz; per-frame logits "
              "bitwise-equal to the sequential run")
        rep = serve_stream(stream_sim, frames,  # closed-loop front-end
                           batch_window=args.batch_window)
        pct = rep.latency_percentiles()
        print(f"closed-loop at the pipeline's own rate "
              f"({rep.offered_inf_s:.3g} req/s): latency p50/p99 = "
              f"{pct['p50']:.0f}/{pct['p99']:.0f} cycles, measured "
              f"throughput {rep.throughput_inf_s:.3g} inf/s")
        sizes = ", ".join(str(s) for s in rep.batch_sizes)
        print(f"  micro-batches (batch_window="
              f"{args.batch_window or 'unbounded'}): [{sizes}] — "
              "per-request latency comes from the timing model, so "
              "batching never moves a reported cycle")
        if prof is not None:
            from repro.telemetry.spans import stream_timeline_events

            stage_names = [cnn.layers[st.li].name
                           for st in stream_sim._stages]
            timeline_events = stream_timeline_events(sres, stage_names)

    # 7) optional: the same network under an injected DSE placement —
    # identical logits (bitwise), shorter routes (snake prints the
    # trivial +0.0% baseline-vs-itself line rather than doing nothing)
    if args.placement:
        from repro.dse.placements import strategies, validate_placement

        full_plan = plan_network(cnn)  # the simulator's reuse=1 plan
        strat = strategies(cnn)[args.placement]
        opt_placement = strat.place(full_plan)
        assert validate_placement(full_plan, opt_placement) == []
        opt = NetworkSimulator(cnn, int_params, backend="trace",
                               placement=opt_placement).run(xb)
        assert np.array_equal(opt.logits, res.logits), \
            "placement changed the math?!"
        base_total = sum(res.traffic.byte_hops.values())
        opt_total = sum(opt.traffic.byte_hops.values())
        print(f"placement={args.placement} "
              f"(mesh {opt_placement.noc.rows}x{opt_placement.noc.cols}): "
              f"logits bitwise-equal; routed byte-hops "
              f"{base_total} -> {opt_total} "
              f"({100 * (opt_total / base_total - 1):+.1f}%), "
              "per class: " + ", ".join(
                  f"{k}={v}" for k, v in sorted(opt.traffic.byte_hops.items())))

    if prof is not None:
        from repro.telemetry.spans import write_chrome_trace

        prof.uninstall()
        write_chrome_trace(args.trace_out, prof.events + timeline_events)
        print(f"wrote {args.trace_out}: "
              f"{len(prof.events) + len(timeline_events)} trace events — "
              "open in https://ui.perfetto.dev")


if __name__ == "__main__":
    main()
