"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

Runs the host loop (repro.train.loop) on the local device set for any
registered strategy (``--strategy ambdg|amb|kbatch|decentralized``).
On a real pod this process runs per-host under the usual multi-host
runtime (jax.distributed.initialize) with the same code path; CI runs
a reduced config on CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Tuple

import repro.configs as C
from repro.api import available_strategies
from repro.configs.base import (AmbdgConfig, BatchScheduleConfig,
                                ConsensusConfig, DelayConfig,
                                ElasticConfig, MeshConfig, RunConfig,
                                SHAPES)
from repro.core.batch_schedule import BATCH_SCHEDULES
from repro.core.delay_process import DELAY_PROCESSES
from repro.core.worker_process import WORKER_PROCESSES
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.models.api import Model
from repro.train.loop import LoopConfig, train


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU)")
    ap.add_argument("--strategy", default="ambdg",
                    choices=available_strategies(),
                    help="algorithm variant (Strategy registry)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--optimizer", default="dual_averaging")
    ap.add_argument("--tau", type=int, default=1)
    ap.add_argument("--t-p", type=float, default=2.5)
    ap.add_argument("--t-c", type=float, default=10.0)
    ap.add_argument("--n-microbatches", type=int, default=2)
    ap.add_argument("--delay-process", default="fixed",
                    choices=sorted(DELAY_PROCESSES),
                    help="staleness process of the master exchange: "
                         "'fixed' = the paper's constant tau; the "
                         "stochastic processes run the delay-tolerant "
                         "ring (ambdg only)")
    ap.add_argument("--tau-max", type=int, default=0,
                    help="staleness cap sizing the delay-tolerant ring "
                         "(0 = 2*tau for stochastic processes)")
    ap.add_argument("--delay-min", type=int, default=1)
    ap.add_argument("--delay-seed", type=int, default=0)
    ap.add_argument("--elastic-process", default="static",
                    choices=sorted(WORKER_PROCESSES),
                    help="elastic-worker process: 'static' = the "
                         "exact fixed-fleet path; 'heterogeneous' = "
                         "persistent speed skew; 'churn' = up/down "
                         "Gilbert-Elliott chain; 'crash_restart' = "
                         "exponential MTTF/MTTR")
    ap.add_argument("--churn-rate", type=float, default=0.05,
                    help="per-epoch failure probability "
                         "(ElasticConfig.p_fail, churn process)")
    ap.add_argument("--churn-recover", type=float, default=0.5,
                    help="per-epoch recovery probability "
                         "(ElasticConfig.p_recover, churn process)")
    ap.add_argument("--elastic-seed", type=int, default=0,
                    help="seed of the elastic worker process")
    ap.add_argument("--batch-schedule", default="fixed",
                    choices=sorted(BATCH_SCHEDULES),
                    help="adaptive minibatch schedule b(t): 'fixed' = "
                         "the exact timing-driven anytime path; "
                         "'linear' ramps, 'adadamp' grows as the loss "
                         "drops, 'delay_aware' scales with observed "
                         "staleness (alpha takes b(t) for b_bar)")
    ap.add_argument("--batch-b0", type=int, default=0,
                    help="schedule base target b(1) "
                         "(0 = round(b_bar) = n_workers * "
                         "samples_per_worker)")
    ap.add_argument("--batch-cap", type=int, default=0,
                    help="cap on scheduled targets (0 = 16 * b0)")
    ap.add_argument("--batch-growth", type=float, default=1.0,
                    help="linear schedule: +samples per step")
    ap.add_argument("--batch-schedule-seed", type=int, default=0,
                    help="seed of the batch-size controller")
    ap.add_argument("--fixed-alpha", action="store_true",
                    help="disable the Agarwal-Duchi delay-adaptive "
                         "step size (use the static worst-case tau)")
    ap.add_argument("--topology", default="ring",
                    help="decentralized gossip topology")
    ap.add_argument("--gossip-rounds", type=int, default=0,
                    help="decentralized: 0 derives eq. (24)'s bound")
    ap.add_argument("--gossip-compression", default="none",
                    choices=("none", "int8"),
                    help="decentralized: compress gossip messages to "
                         "int8 + per-row scales with error feedback")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--n-workers", type=int, default=4)
    ap.add_argument("--samples-per-worker", type=int, default=4)
    return ap


def build_run(args: argparse.Namespace) -> Tuple[Model, RunConfig,
                                                 LoopConfig]:
    """The model, run config and loop config the parsed flags name."""
    model_cfg = (C.get_smoke_config(args.arch) if args.smoke
                 else C.get_config(args.arch))
    shape = SHAPES[args.shape]
    if args.smoke and args.seq_len is None:
        args.seq_len = 128          # CPU-friendly default for --smoke
    if args.seq_len or args.batch:
        shape = dataclasses.replace(
            shape, seq_len=args.seq_len or shape.seq_len,
            global_batch=args.batch or shape.global_batch)

    total = args.n_workers * args.samples_per_worker
    shape = dataclasses.replace(shape, global_batch=total)

    rc = RunConfig(
        model=model_cfg, shape=shape,
        mesh=MeshConfig(n_pods=1, data=1, model=1),
        ambdg=AmbdgConfig(t_p=args.t_p, t_c=args.t_c, tau=args.tau,
                          n_microbatches=args.n_microbatches,
                          b_bar=float(total)),
        strategy=args.strategy,
        consensus=ConsensusConfig(topology=args.topology,
                                  n_workers=args.n_workers,
                                  rounds=args.gossip_rounds,
                                  compression=args.gossip_compression),
        delay=DelayConfig(
            process=args.delay_process,
            tau_max=args.tau_max or (2 * args.tau
                                     if args.delay_process != "fixed"
                                     else 0),
            delay_min=args.delay_min, seed=args.delay_seed,
            adaptive_alpha=not args.fixed_alpha),
        elastic=ElasticConfig(process=args.elastic_process,
                              p_fail=args.churn_rate,
                              p_recover=args.churn_recover,
                              seed=args.elastic_seed),
        batch_schedule=BatchScheduleConfig(
            schedule=args.batch_schedule, b0=args.batch_b0,
            b_cap=args.batch_cap, growth_rate=args.batch_growth,
            seed=args.batch_schedule_seed),
        optimizer=args.optimizer)
    loop = LoopConfig(n_steps=args.steps, ckpt_dir=args.ckpt_dir,
                      n_workers=args.n_workers,
                      samples_per_worker=args.samples_per_worker)
    return build_model(model_cfg), rc, loop


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    model, rc, loop = build_run(args)
    out = train(model, rc, loop, log_fn=lambda m: print(json.dumps(m)))
    print(f"done: {len(out['history'])} log points, "
          f"final loss {out['history'][-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
