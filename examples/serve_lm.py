"""Serving driver: batched prefill + decode with KV cache, plus the
RowClone-analog KV-page fork, and the DRAM-level cost of the same fork
evaluated by the EasyDRAM engine (framework <-> paper tie-in).

  PYTHONPATH=src python examples/serve_lm.py --arch rwkv6_3b
"""
import argparse
import time
import warnings

warnings.filterwarnings("ignore")

import jax
import numpy as np

from repro.configs import SSMConfig, get_config
from repro.core import emulator, traces
from repro.core.dram import Geometry
from repro.core.profiling import DeviceModel
from repro.core.timescale import JETSON_NANO
from repro.models import model_zoo
from repro.serve.engine import ServeEngine

REDUCE = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
              vocab_size=512, head_dim=32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_8b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--new", type=int, default=16)
    args = ap.parse_args()

    over = dict(REDUCE)
    cfg0 = get_config(args.arch)
    if cfg0.attn_free:
        over["n_kv_heads"] = over["n_heads"]
        over["ssm"] = SSMConfig(chunk=16)
    cfg = cfg0.scaled(**over)
    model = model_zoo.build(cfg, s_max=64)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, s_max=64)

    prompts = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(args.batch, 16))
    t0 = time.perf_counter()
    outs = engine.generate_batch(prompts, args.new)
    dt = time.perf_counter() - t0
    print(f"{cfg.name}: {args.batch} reqs x {args.new} tokens "
          f"in {dt:.2f}s ({args.batch*args.new/dt:.1f} tok/s)")
    print("first continuation:", outs[0].tolist())

    # KV page fork: on-TPU analogue (Pallas copy kernel path)...
    _, cache = model.prefill_fn(params, {"tokens": prompts[:1]})
    forked = engine.fork_cache(cache, 4, use_kernel=True)
    print("forked cache x4:",
          jax.tree_util.tree_leaves(forked)[0].shape)

    # ...and the same fork's DRAM cost under the EasyDRAM engine — both
    # arms batched through one run_many campaign step
    dev = DeviceModel(Geometry())
    tr_rc, _ = traces.kv_fork_trace(16, 8192, Geometry(), "rowclone", dev)
    tr_cpu, _ = traces.kv_fork_trace(16, 8192, Geometry(), "cpu", dev)
    a, b = emulator.run_many([tr_cpu, tr_rc], JETSON_NANO, "ts")
    print(f"DRAM-level fork (16 pages): cpu={int(a['exec_cycles'])} cyc, "
          f"rowclone={int(b['exec_cycles'])} cyc "
          f"({int(a['exec_cycles'])/max(int(b['exec_cycles']),1):.1f}x)")


if __name__ == "__main__":
    main()
