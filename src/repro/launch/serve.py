"""Serving launcher — a thin CLI over the ``repro.serve`` engine
(batched prefill + continuous-batching decode for any architecture's
reduced config, run for real on this host).

``--disagg`` serves through the disaggregated front instead: prefill
specialists feeding decode engines over the cache-migration channel,
with the per-request migrate-vs-local decision priced by the Table-2
cost model (see ``repro.serve.disagg``).

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch xlstm-1.3b \
      --batch 4 --prompt-len 64 --gen 32
  PYTHONPATH=src python -m repro.launch.serve --disagg \
      --decode-engines 2 --prefill-gmis 1 --batch 8
"""
from __future__ import annotations

import argparse

from repro.configs import get_reduced
from repro.configs.base import InputShape
from repro.data import make_batch
from repro.models import transformer as T
from repro.serve import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--disagg", action="store_true",
                    help="serve through the disaggregated prefill/decode "
                         "front (cache migration over repro.comm)")
    ap.add_argument("--decode-engines", type=int, default=2,
                    help="decode GMIs behind the router (--disagg)")
    ap.add_argument("--prefill-gmis", type=int, default=1,
                    help="prefill-specialist GMIs (--disagg)")
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    cfg = get_reduced(args.arch)
    if cfg.is_encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only: no decode serving "
                         f"(repro.configs.shape_skips)")
    params = T.init_model(jax.random.key(args.seed), cfg)
    shape = InputShape("serve", args.prompt_len, args.batch, "prefill")
    batch = make_batch(cfg, shape, seed=args.seed)

    requests = []
    for i in range(args.batch):
        extras = {"patches": batch["patches"][i]} \
            if cfg.frontend == "vision" else None
        requests.append(Request(tokens=batch["tokens"][i],
                                max_new_tokens=args.gen,
                                temperature=args.temperature,
                                seed=args.seed + i, extras=extras))

    if args.disagg:
        from repro.launch.steps import make_disagg_front
        front = make_disagg_front(
            cfg, params, decode_engines=args.decode_engines,
            prefill_gmis=args.prefill_gmis, max_slots=args.batch,
            max_seq=args.prompt_len + args.gen + 8)
        done = front.serve(requests)
        load = front.take_epoch()
        pl = front.planner
        print(f"arch={args.arch} batch={args.batch} disagg: "
              f"{args.prefill_gmis} prefill + {args.decode_engines} "
              f"decode GMI(s)")
        print(f"migrated={pl.migrated} local={pl.kept_local} "
              f"bw={pl.bandwidth/1e9:.2f} GB/s "
              f"prefill_rate={pl.prefill_tok_s:,.0f} tok/s")
        print(f"tokens={load.tokens} p50={load.p50_s*1e3:.1f} ms "
              f"p95={load.p95_s*1e3:.1f} ms")
        first = next(c for c in done if c.rid == requests[0].rid)
        print("sample token ids:", first.tokens[:16])
        return

    engine = ServeEngine(cfg, params, max_slots=args.batch,
                         max_seq=args.prompt_len + args.gen + 8)
    done = engine.serve(requests)

    tel = engine.telemetry
    B = args.batch
    prompt_tokens = len(requests[0].tokens) + (
        cfg.num_patches if cfg.frontend == "vision" else 0)
    gen_tokens = tel.total_tokens - B          # B first tokens are prefill's
    first = next(c for c in done if c.rid == requests[0].rid)
    print(f"arch={args.arch} batch={B} prompt={prompt_tokens} "
          f"gen={args.gen}")
    print(f"prefill: {tel.total_prefill_s*1e3:.1f} ms "
          f"({tel.total_prompt_tokens/max(tel.total_prefill_s,1e-9):,.0f} "
          f"tok/s)")
    print(f"decode:  {tel.total_decode_s*1e3:.1f} ms "
          f"({gen_tokens/max(tel.total_decode_s,1e-9):,.0f} tok/s)")
    print("sample token ids:", first.tokens[:16])


if __name__ == "__main__":
    main()
