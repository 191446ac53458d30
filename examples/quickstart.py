"""Quickstart: train a tiny GQA LM on synthetic data, checkpoint, serve
a few greedy tokens, then run a batched DRAM-emulation campaign — the
whole public API in ~60 lines.

The emulation side has three entry points: ``emulator.run`` for one
(trace, system, mode) point, ``emulator.run_many`` /
``campaign.Campaign`` for sweeps — a Campaign collects grid points,
groups them by compile key (trace bucket, SystemConfig, mode, Bloom
shape), and executes each group as one vmapped jit call, so a sweep
compiles once per group instead of once per point — and
``emulator.run_stream`` / ``run_stream_many`` for unbounded traces,
which scan constant-memory windows through one length-independent
executable and stay bit-identical to single-shot.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import traces
from repro.core.campaign import Campaign
from repro.core.dram import Geometry
from repro.core.timescale import JETSON_NANO
from repro.data.pipeline import ShardedLoader, SyntheticLM
from repro.models import model_zoo
from repro.serve.engine import ServeEngine
from repro.train import optimizer as opt
from repro.train.trainer import Trainer


def main():
    cfg = get_config("qwen3_8b").scaled(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
        vocab_size=256, head_dim=32)
    model = model_zoo.build(cfg, s_max=64)
    print(f"arch={cfg.name} (reduced) params={model.n_params():,}")

    src = SyntheticLM(cfg.vocab_size, seq_len=32, global_batch=16, seed=0)
    trainer = Trainer(model, opt.AdamWConfig(lr=1e-2, warmup=10, total_steps=300),
                      ckpt_dir="/tmp/repro_quickstart", ckpt_every=50)
    state, restored = trainer.restore_or_init()
    print("restored from checkpoint" if restored else "fresh init")
    state, hist = trainer.run(state, iter(ShardedLoader(src)), steps=60,
                              log_every=20)
    print(f"loss: {hist[0]:.3f} -> {hist[-1]:.3f}")

    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), state.master)
    engine = ServeEngine(model, params, s_max=64)
    prompt = np.asarray(src.batch(0)["tokens"])[0, :16]
    out = engine.generate(prompt, max_new=16)
    print("generated:", out)

    # batched emulation campaign: sweep PolyBench kernels x {ts, nots}
    # in grouped vmapped calls (one compile per group, not per point)
    geo = Geometry()
    camp = Campaign()
    for i, kern in enumerate(traces.POLYBENCH[:3]):
        tr, _ = traces.polybench_trace(kern, geo, max_accesses=2000, seed=i)
        if tr is None:
            continue
        for mode in ("ts", "nots"):
            camp.add(tr, JETSON_NANO, mode=mode, kernel=kern.name)
    print(f"\ncampaign: {len(camp)} points in {camp.n_groups()} compile groups")
    for r in camp.run():
        print(f"  {r['kernel']:>10s} {r['mode']:>4s}: "
              f"{int(r['exec_cycles']):>9d} cycles")

    # unbounded traces stream through constant-memory windows: the
    # generator below never materializes its 50k requests, the compiled
    # window executable is length-independent (one compile key for any
    # trace length), and the result is bit-identical to single-shot run
    from repro.core.emulator import run_stream
    stream = traces.synthetic_stream(50_000, window=4096, seed=7)
    r = run_stream(stream, JETSON_NANO, "ts", collect="aggregate")
    print(f"\nstreamed {int(r['n_requests']):,} requests: "
          f"{int(r['exec_cycles']):,} cycles, "
          f"avg load latency {r['avg_load_latency_cycles']:.1f} cycles")

    # scheduling policies are software too (see examples/policy_lab.py
    # for the full lab): author one, cost it, run it
    from repro.core.smcprog import PolicyBuilder
    b = PolicyBuilder()
    prog = b.build(score=b.score_age(), boost=b.score_row_hit(),
                   name="my-frfcfs")
    tr, _ = traces.polybench_trace(traces.POLYBENCH[0], geo,
                                   max_accesses=2000, seed=0)
    from repro.core.emulator import run
    r = run(tr, JETSON_NANO.with_policy(prog), "ts")
    print(f"\npolicy {prog.name} ({prog.smc_cycles()} smc-cycles/decision): "
          f"{int(r['exec_cycles'])} cycles")

    # deterministic fault injection (PR 8): attach a FaultModel and the
    # engine reports bit flips — RowHammer disturbance + retention
    # failures — reproducibly (same seed => same flip set, across every
    # engine). Mitigations are policy programs: counter-based TRR below
    # suppresses the flips at a small neighbor-refresh slowdown cost.
    from repro.core.faults import FaultModel
    from repro.core.smcprog import mitigation_programs
    fm = FaultModel(seed=7, hammer_threshold=32, hammer_flip_fp=52000)
    storm = traces.rowhammer_trace(2000, geo, intensity=0.85, seed=1)
    plain = run(storm, JETSON_NANO.with_faults(fm), "ts")
    trr = mitigation_programs(trr_threshold=16)["trr16"]
    guarded = run(storm, JETSON_NANO.with_policy(trr).with_faults(fm), "ts")
    print(f"\nrowhammer storm unmitigated: {int(plain['flips'])} flips "
          f"(BER {float(plain['bit_error_rate']):.4f})")
    print(f"with TRR policy: {int(guarded['flips'])} flips, "
          f"{int(guarded['mitigations'])} neighbor refreshes, "
          f"{int(guarded['exec_cycles']) / int(plain['exec_cycles']):.3f}x "
          f"cycles")

    # shared sweep server (ISSUE 9): many clients, one warm engine —
    # compatible points from different clients coalesce into shared
    # batched dispatches, bit-identical to a direct Campaign.run (see
    # examples/sweep_service.py for the full two-client walkthrough)
    from repro.service import SweepClient, SweepServer
    with SweepServer() as srv:
        cli = SweepClient(server=srv, name="quickstart")
        for i in range(3):
            t, _ = traces.polybench_trace(traces.POLYBENCH[i], geo,
                                          max_accesses=500, seed=i)
            cli.submit(t, JETSON_NANO, mode="ts", workload=i)
        recs = cli.collect()
        st = srv.stats()
    print(f"\nsweep service: {len(recs)} points in "
          f"{st['dispatches']['count']} dispatch(es), "
          f"p50 latency {st['latency_ms']['p50']:.1f} ms")


if __name__ == "__main__":
    main()
