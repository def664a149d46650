"""Gossip wire-bytes benchmark: per topology x compression.

The decentralized strategy's cost on real multi-host topologies is the
per-round ppermute payload (ROADMAP: the DCN bound). This benchmark
compiles the ACTUAL shard_map gossip programs (16 virtual CPU devices,
forced at import exactly like the dry-run) and reports, per topology
(ring/torus/complete) and compression mode (none/int8):

  * measured per-round collective-permute wire bytes, parsed from the
    optimized HLO by the shared census in ``repro.launch.hlo`` (the
    rounds run under ``lax.scan``, whose body appears once in the HLO
    module — so the census IS per-round bytes, independent of r);
  * the analytic payload model (``consensus.payload_bytes_per_round``)
    — the two must agree, or the census/model has rotted;
  * the consensus error both modes reach after the SAME eq.-(24)
    round count on unit-norm messages (matched tolerance: the int8
    error-feedback path must land in the same regime, not just move
    fewer bytes);
  * wall-clock time of the r-round exchange.

Emits ``name,metric,value`` CSV rows (run.py contract) and writes
``BENCH_gossip.json`` so the payload trajectory is tracked across PRs
alongside ``BENCH_master_update.json``.
"""
from __future__ import annotations

import os

# No-clobber: a device count already pinned in XLA_FLAGS (or injected
# via REPRO_HOST_DEVICES) wins; only the bare default forces the 16
# virtual devices the topology table below needs.
from repro.launch.xla import ensure_host_platform_device_count
ensure_host_platform_device_count(default=16)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core import consensus
from repro.dist.sharding import gossip_specs
from repro.launch.hlo import collective_bytes
from repro.launch.mesh import make_mesh

ROWS = 256          # message rows: (rows, 128) per worker, ~131 KB f32
DELTA, J = 0.05, 1.0


def bench_topology(topology: str, n: int, rows: int = ROWS) -> dict:
    Q = consensus.gossip_matrix(topology, n)
    lam2 = consensus.lambda2(Q)
    r = consensus.min_rounds(DELTA, n, J, lam2)
    mesh = make_mesh((n,), ("worker",))
    sp = gossip_specs().msg

    rng = np.random.default_rng(0)
    v = rng.standard_normal((n, rows, 128)).astype(np.float32)
    v = v / np.linalg.norm(v.reshape(n, -1), axis=1)[:, None, None] * J
    v = jnp.asarray(v)
    res0 = jnp.zeros_like(v)

    result = {"topology": topology, "n_workers": n, "rows": rows,
              "lambda2": round(lam2, 6), "rounds_eq24": r,
              "delta": DELTA, "modes": {}}
    for compression in ("none", "int8"):
        if compression == "int8":
            def local(x, res):
                return consensus.gossip_rounds_shard_int8(
                    x, res, "worker", topology, n, r)
        else:
            def local(x, res):
                return consensus.gossip_rounds_shard(
                    x, "worker", topology, n, r), res
        fn = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(sp, sp),
                                   out_specs=(sp, sp), check_vma=False))
        compiled = fn.lower(v, res0).compile()
        coll = collective_bytes(compiled.as_text())
        # the SPMD program text is per-device, so the census is
        # already per-worker — directly comparable to the model
        wire_per_round = coll["collective-permute"]
        analytic = consensus.payload_bytes_per_round(
            topology, n, rows, compression=compression)
        z, _ = compiled(v, res0)
        jax.block_until_ready(z)
        t0 = time.perf_counter()
        iters = 5
        for _ in range(iters):
            z, rout = compiled(v, res0)
        jax.block_until_ready(z)
        dt = (time.perf_counter() - t0) / iters
        err = float(consensus.consensus_error(
            jnp.reshape(z, (n, -1))))
        result["modes"][compression] = {
            "wire_bytes_per_round": int(wire_per_round),
            "analytic_bytes_per_round": int(analytic),
            "consensus_error_at_r": err,
            "exchange_seconds": round(dt, 6),
        }
    none_b = result["modes"]["none"]["wire_bytes_per_round"]
    int8_b = result["modes"]["int8"]["wire_bytes_per_round"]
    result["payload_reduction"] = round(none_b / int8_b, 3)
    return result


def run() -> None:
    results = []
    for topology, n in (("ring", 8), ("torus", 16), ("complete", 8)):
        r = bench_topology(topology, n)
        results.append(r)
        tag = f"gossip_{topology}"
        emit(tag, "rounds_eq24", r["rounds_eq24"])
        for mode, m in r["modes"].items():
            emit(tag, f"wire_bytes_per_round_{mode}",
                 m["wire_bytes_per_round"])
            emit(tag, f"consensus_error_{mode}",
                 round(m["consensus_error_at_r"], 6))
        emit(tag, "payload_reduction", r["payload_reduction"])
        # the acceptance gates this trajectory exists to pin: the
        # measured census matches the analytic wire model, >= 3.5x
        # payload reduction, at matched consensus-error tolerance
        for mode, m in r["modes"].items():
            assert (m["wire_bytes_per_round"]
                    == m["analytic_bytes_per_round"]), (topology, mode, m)
        assert r["payload_reduction"] >= 3.5, r
        assert (r["modes"]["int8"]["consensus_error_at_r"]
                <= 2 * DELTA), r
    with open("BENCH_gossip.json", "w") as f:
        json.dump({"results": results}, f, indent=1)


if __name__ == "__main__":
    run()
