"""Serving launcher: ``python -m repro.launch.serve --arch <id> --smoke``

Drives the continuous-batching engine under a seeded open-loop arrival
process (``rc.serve``: Poisson or bursty traffic), optionally with the
bounded-staleness weight-publication channel attached (--publish-period
> 0 simulates the master publishing every N steps and the engine
popping the freshest due snapshot). Runs on the local device set
(reduced config on CPU); the production-shape decode program is
exercised by the dry-run: ``repro.launch.dryrun`` lowers
``continuous_decode_step`` + the publish pop for decode_32k /
long_500k on the 256/512-chip meshes.
"""
from __future__ import annotations

import argparse

import repro.configs as C
from repro.configs.base import ServeConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serve import Engine, RequestQueue, WeightPublisher


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--n-steps", type=int, default=64)
    ap.add_argument("--arrival", default="poisson",
                    choices=("poisson", "bursty"))
    ap.add_argument("--arrival-rate", type=float, default=0.5)
    ap.add_argument("--publish-period", type=int, default=0,
                    help="master steps between weight publishes "
                         "(0 = channel off)")
    ap.add_argument("--staleness-bound", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (C.get_smoke_config(args.arch) if args.smoke
           else C.get_config(args.arch))
    model = build_model(cfg)
    if model.decode_step is None:
        raise SystemExit(f"{args.arch} has no decode path")
    sc = ServeConfig(slots=args.slots, max_len=args.max_len,
                     max_new=args.max_new, arrival=args.arrival,
                     arrival_rate=args.arrival_rate,
                     publish_period=args.publish_period,
                     staleness_bound=args.staleness_bound,
                     seed=args.seed)
    engine = Engine(model, sc.slots, sc.max_len, seed=sc.seed)
    queue = RequestQueue(sc, cfg.vocab_size)

    publisher = None
    if sc.publish_period > 0:
        from repro.core.arena import make_layout
        publisher = WeightPublisher(make_layout(engine.params), sc)
        engine.attach_publisher(publisher)

    for t in range(args.n_steps):
        if publisher is not None and t % sc.publish_period == 0:
            # stand-in master: republish the engine's own weights on
            # the publish clock so the pop/staleness path is exercised
            publisher.publish(engine.params, t)
            engine.refresh_weights(t)
        queue.step()
        engine.step(queue)

    s = engine.stats
    print(f"steps={s.steps} submitted={queue.submitted} "
          f"admitted={s.admitted} completed={s.completed} "
          f"in_flight={engine.in_flight} queued={len(queue)}")
    print(f"prefill_tok={s.prefill_tokens} decode_tok={s.decode_tokens}")
    if publisher is not None:
        print(f"publish: pops={s.publish_pops} misses={s.publish_misses} "
              f"staleness mean={s.staleness_mean():.2f} "
              f"max={s.staleness_max} (bound={sc.staleness_bound})")
    for rid, toks in engine.completions[:4]:
        print(f"req {rid}: {len(toks)} tokens")


if __name__ == "__main__":
    main()
