# Virtual-device count for this process's cells. No-clobber: a count
# already pinned in XLA_FLAGS (a CI leg, the sweep's subprocess env)
# wins; REPRO_HOST_DEVICES injects one; the bare default covers the
# smallest cell group. One process = one count (XLA reads the flag
# once), hence benchmarks/matrix_sweep.py runs one subprocess per
# device-count group. Must run before the first jax backend touch.
from repro.launch.xla import ensure_host_platform_device_count
HOST_DEVICES = ensure_host_platform_device_count(default=64)

"""Scenario-matrix scale harness (docs/matrix.md).

One runner enumerating cells of

    strategy x model config x delay process x compression x mesh shape

at 8-512 virtual devices, reusing ``launch.dryrun.run_cell`` (which
reuses ``lower_train`` / ``lower_serve`` / ``lower_publish_pop``) for
the full-step lowering and metrics, and asserting three HLO-level
invariants per cell — not just "it compiled":

  A. zero ring-dtype copy instructions (the arena donation contract of
     docs/arena.md); the known staging-fill layout copies are
     attributed via HLO source metadata and REPORTED, not hidden (see
     docs/matrix.md — the finding this harness flushed out);
  B. compressed DCN edges: with int8 on, the exchange program's only
     non-s8 wire bytes are the per-row scales;
  C. the strict ``collective_bytes`` census of the cell's exchange
     program == the closed-form wire model (``launch.wire_model``),
     exactly, per dtype.

Usage (device count must equal each cell's mesh size — the sweep
groups cells per count and spawns one subprocess per group):

  PYTHONPATH=src REPRO_HOST_DEVICES=64 python -m repro.launch.matrix \
      --devices 64 --all --json out.json
  PYTHONPATH=src python -m repro.launch.matrix --list
"""
import argparse
import inspect
import json
import sys
import time
from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import repro.configs as C
from repro.configs.base import (AmbdgConfig, ConsensusConfig, DelayConfig,
                                RunConfig, ShapeConfig)
from repro.core import arena as arena_mod
from repro.core import consensus
from repro.dist import shapes_and_axes
from repro.launch import dryrun
from repro.launch import wire_model
from repro.launch.hlo import (collective_bytes, collective_bytes_by_dtype,
                              copy_bytes, copy_records, copy_shapes)
from repro.launch.mesh import make_mesh, mesh_label, parse_mesh
from repro.models import build_model

# Matrix smoke shapes: small enough that every big-config smoke
# variant lowers+compiles in seconds at 512 virtual devices, large
# enough that every mesh axis divides the batch.
MATRIX_TRAIN = ShapeConfig("matrix_train_smoke", 128, 64, "train")
MATRIX_DECODE = ShapeConfig("matrix_decode_smoke", 512, 64, "decode")

GOSSIP_ROUNDS = 2   # census is per-round (scan body), r only pads compile


@dataclass(frozen=True)
class MatrixCell:
    name: str
    arch: str               # smoke-config id (C.get_smoke_config)
    mesh: str               # parse_mesh spec; prod == device count
    strategy: str = "ambdg"
    kind: str = "train"     # "train" | "decode"
    tau: int = 1
    delay_process: str = "fixed"
    tau_max: Optional[int] = None      # explicit-only (dryrun contract)
    pod_compression: str = "none"      # master DCN compression
    gossip_compression: str = "none"   # decentralized wire compression
    topology: str = "ring"
    n_workers: int = 8
    n_microbatches: int = 2

    @property
    def devices(self) -> int:
        cfg = parse_mesh(self.mesh)
        return cfg.n_devices


# The default matrix. Axes covered: 4 strategies, 7 big-config smoke
# variants, 3 delay processes, both compression modes (master int8 DCN
# + gossip int8), 8 mesh shapes at 8/64/128/512 virtual devices.
# NOTE int8 pod compression is only paired with the FIXED delay
# process: the delay-tolerant (v3) ring folds int8 locally and ships
# one f32 psum across DCN, so a compressed-DCN-edge invariant on a
# variable-delay cell is unsatisfiable by construction (docs/matrix.md).
CELLS = (
    # -- 8 devices: the cheap CI-smoke group --------------------------
    MatrixCell("m8-ambdg-qwen15-2x2x2-int8", "qwen1.5-0.5b", "2x2x2",
               tau=1, pod_compression="int8"),
    MatrixCell("m8-decentralized-xlstm-2x4-int8", "xlstm-125m", "2x4",
               strategy="decentralized", n_workers=8,
               gossip_compression="int8"),
    # -- 64 devices ---------------------------------------------------
    MatrixCell("m64-ambdg-mixtral8x22b-2x4x8-f32", "mixtral-8x22b",
               "2x4x8", tau=1),
    MatrixCell("m64-amb-chatglm-2x4x8", "chatglm3-6b", "2x4x8",
               strategy="amb"),
    MatrixCell("m64-kbatch-zamba2-8x8", "zamba2-2.7b", "8x8",
               strategy="kbatch"),
    MatrixCell("m64-decentralized-xlstm-8x8-f32", "xlstm-125m", "8x8",
               strategy="decentralized", n_workers=8),
    MatrixCell("m64-decentralized-xlstm-8x8-int8", "xlstm-125m", "8x8",
               strategy="decentralized", n_workers=8,
               gossip_compression="int8"),
    MatrixCell("m64-ambdg-seamless-2x4x8-int8", "seamless-m4t-large-v2",
               "2x4x8", tau=2, pod_compression="int8"),
    MatrixCell("m64-ambdg-qwen3-2x4x8-jitter", "qwen3-1.7b", "2x4x8",
               delay_process="jitter", tau_max=4),
    # -- 128 devices --------------------------------------------------
    MatrixCell("m128-ambdg-chatglm-2x8x8-int8", "chatglm3-6b", "2x8x8",
               tau=2, pod_compression="int8"),
    MatrixCell("m128-ambdg-mixtral8x22b-2x8x8-heavytail",
               "mixtral-8x22b", "2x8x8", delay_process="heavy_tail",
               tau_max=6),
    MatrixCell("m128-kbatch-seamless-2x8x8", "seamless-m4t-large-v2",
               "2x8x8", strategy="kbatch"),
    MatrixCell("m128-serve-zamba2-16x8", "zamba2-2.7b", "16x8",
               kind="decode"),
    MatrixCell("m128-decentralized-qwen15-8x16-torus-int8",
               "qwen1.5-0.5b", "8x16", strategy="decentralized",
               topology="torus", n_workers=16,
               gossip_compression="int8"),
    # -- 512 devices: the production multi-pod shape ------------------
    MatrixCell("m512-ambdg-chatglm-2x16x16-int8", "chatglm3-6b",
               "2x16x16", tau=2, pod_compression="int8"),
    MatrixCell("m512-ambdg-seamless-2x16x16-bursty",
               "seamless-m4t-large-v2", "2x16x16",
               delay_process="bursty", tau_max=4),
)

CELLS_BY_NAME = {c.name: c for c in CELLS}


def build_cell_rc(cell: MatrixCell) -> RunConfig:
    """The cell's RunConfig on its SMOKE model config (the big-config
    smoke variants are the whole point: nothing else exercises them
    end-to-end)."""
    shape = MATRIX_TRAIN if cell.kind == "train" else MATRIX_DECODE
    tau = 0 if cell.strategy in ("amb", "kbatch") else cell.tau
    rc = RunConfig(
        model=C.get_smoke_config(cell.arch),
        shape=shape,
        mesh=parse_mesh(cell.mesh),
        strategy=cell.strategy,
        ambdg=AmbdgConfig(tau=tau, n_microbatches=cell.n_microbatches,
                          pod_compression=cell.pod_compression),
        consensus=ConsensusConfig(topology=cell.topology,
                                  n_workers=cell.n_workers,
                                  compression=cell.gossip_compression),
    )
    if cell.delay_process != "fixed":
        rc = rc.replace(delay=DelayConfig(process=cell.delay_process,
                                          tau_max=cell.tau_max))
    return rc


def _arena_rows(rc: RunConfig) -> int:
    model = build_model(rc.model)
    params_shapes, _ = shapes_and_axes(model.init, jax.random.PRNGKey(0))
    return arena_mod.make_layout(params_shapes).rows


# ---------------------------------------------------------------------------
# Invariant A: zero ring-dtype copies (docs/arena.md donation contract)
# ---------------------------------------------------------------------------
def _staging_fill_spans():
    """Source-line spans of the arena staging fill (``flatten_tree`` /
    ``scatter_fed``): per-leaf row-offset update-slices that GSPMD
    cannot keep row-sharded at scale, producing layout copies on
    STAGING-shaped tensors. Computed via ``inspect`` so the allowlist
    tracks the code instead of hardcoded line numbers."""
    spans = []
    for fn in (arena_mod.flatten_tree, arena_mod.scatter_fed):
        src, start = inspect.getsourcelines(fn)
        spans.append((start, start + len(src)))
    return spans


def _attribute_copy(rec: Dict, spans) -> Optional[str]:
    """Attribute a copy to one of the KNOWN per-leaf-slicing classes
    (docs/matrix.md — the finding this harness filed), or None if it
    is unaccounted for:

    ``staging_fill``    layout copies whose source line sits inside
        ``arena.flatten_tree`` / ``arena.scatter_fed`` — the per-leaf
        unaligned row-offset update-slices on the f32 staging buffer —
        or metadata-less copies of a staging-fill fusion's result
        (XLA drops op metadata on copies it inserts at fusion
        boundaries; the producing fusion's name still carries the
        dynamic-update-slice root).
    ``residual_slice``  pure layout copies of the error-feedback
        buffer (parameter op_name ``state.arena.residual``, no source
        line) that XLA inserts before the same per-leaf unaligned
        slices read the residual.  Only the residual parameter is
        exempted — a failed donation of the ring/slot buffers would
        surface under its own ``state.arena.*`` name and still FAIL.
    """
    f, ln = rec.get("source_file"), rec.get("source_line")
    if f and ln is not None and f.endswith("core/arena.py") \
            and any(lo <= ln < hi for lo, hi in spans):
        return "staging_fill"
    if rec.get("op_name") == "state.arena.residual":
        return "residual_slice"
    if (rec.get("op_name") is None
            and "dynamic-update-slice_fusion" in (rec.get("operand") or "")):
        return "staging_fill"
    return None


def _ring_param_aliases(hlo_text: str):
    """Instruction names that ARE the ring parameter, transitively
    through pure same-shape copy chains: the ``state.arena.ring``
    entry parameter and every ``copy`` of it (or of such a copy).
    The matrix's VARIABLE-delay cells use these to attribute the
    stacked ring's pop/push copy-protection pair (docs/matrix.md —
    the single-pass masked fold reads all slots of the same donated
    buffer the push overwrites; arena.GradArena documents this as the
    cost the v2 tuple-of-slots layout exists to avoid)."""
    names = set()
    copies = []   # (own name, operand name)
    for line in hlo_text.splitlines():
        ls = line.strip()
        if " = " not in ls:
            continue
        own = ls.split(" = ", 1)[0]
        if own.startswith("ROOT "):
            own = own[len("ROOT "):]
        own = own.lstrip("%")
        if " parameter(" in ls and 'op_name="state.arena.ring"' in ls:
            names.add(own)
        elif " copy(" in ls:
            toks = [t for t in ls.split("copy(", 1)[1].split()
                    if t.startswith("%")]
            if toks:
                copies.append((own, toks[-1].rstrip("),").lstrip("%")))
    changed = True
    while changed:
        changed = False
        for own, operand in copies:
            if operand in names and own not in names:
                names.add(own)
                changed = True
    return names


def _arena_shape_keys(cell: MatrixCell, rc: RunConfig, rows: int, dt: str):
    """Every "dt[dims]" an arena ring/slot/staging copy could print as,
    global or per-device-local dims."""
    mesh = rc.mesh
    flat = mesh.data * mesh.model
    row_variants = {rows}
    if rows % flat == 0:
        row_variants.add(rows // flat)
    pod_variants = {mesh.n_pods, 1}
    keys = set()
    for p in pod_variants:
        for r in row_variants:
            keys.add(f"{dt}[{p},{r},128]")
    if cell.delay_process != "fixed":   # v3 stacked ring
        depth = (cell.tau_max or 4) + 1
        for p in pod_variants:
            for r in row_variants:
                keys.add(f"{dt}[{depth},{p},{r},128]")
    return keys


def _publish_shape_keys(rc: RunConfig, rows: int):
    flat = rc.mesh.data * rc.mesh.model
    row_variants = {rows}
    if rows % flat == 0:
        row_variants.add(rows // flat)
    return {f"s8[{r},128]" for r in row_variants}


def check_ring_copies(cell: MatrixCell, rc: RunConfig, rows: int,
                      hlo_text: str, publish_hlo: Optional[str]) -> Dict:
    """Invariant A.  Violations are copies of RING-dtype arena-shaped
    tensors (the donation contract of docs/arena.md: the ring must
    rotate without copy traffic).  f32 STAGING-shaped copies — the
    per-leaf-slicing finding of docs/matrix.md — are attributed and
    reported, not violations; on an uncompressed cell the ring IS f32
    and shape-identical to staging, so there only the attributed
    classes are exempt and any unaccounted copy still fails."""
    spans = _staging_fill_spans()
    ring_dt = "s8" if cell.pod_compression == "int8" else "f32"
    if cell.kind == "decode":
        ring_keys = _publish_shape_keys(rc, rows)
        staging_keys = set()
        texts = [t for t in (hlo_text, publish_hlo) if t]
    else:
        ring_keys = _arena_shape_keys(cell, rc, rows, ring_dt)
        staging_keys = _arena_shape_keys(cell, rc, rows, "f32")
        texts = [hlo_text]
    violations = []
    attributed = {"staging_fill": {"count": 0, "bytes": 0},
                  "residual_slice": {"count": 0, "bytes": 0},
                  "stacked_pop_push": {"count": 0, "bytes": 0},
                  "unattributed_staging": {"count": 0, "bytes": 0}}
    for text in texts:
        # the stacked (v3) ring's pop/push copy-protection pair is a
        # DOCUMENTED cost of the single-pass fold on the XLA ref path
        # (arena.GradArena; the TPU kernel handles it in-registers) —
        # attributed on variable-delay cells only, a violation anywhere
        # else (a fixed-delay ring-param copy is a failed donation)
        ring_aliases = (_ring_param_aliases(text)
                        if cell.delay_process != "fixed" else set())
        for rec in copy_records(text):
            is_ring = rec["key"] in ring_keys
            if not is_ring and rec["key"] not in staging_keys:
                continue
            cls = _attribute_copy(rec, spans)
            if cls is None and is_ring:
                op_toks = [t for t in (rec.get("operand") or "").split()
                           if t.startswith("%")]
                if op_toks and op_toks[-1].lstrip("%") in ring_aliases:
                    cls = "stacked_pop_push"
            if cls is None and is_ring:
                violations.append(rec)
            else:
                bucket = cls or "unattributed_staging"
                attributed[bucket]["count"] += 1
                attributed[bucket]["bytes"] += rec["bytes"]
    return {"ok": not violations,
            "checked_keys": sorted(ring_keys),
            "violations": violations,
            # the filed finding, kept visible in BENCH_matrix.json:
            "attributed_copies": attributed}


# ---------------------------------------------------------------------------
# Invariants B + C: the cell's exchange program, census vs wire model
# ---------------------------------------------------------------------------
def _scoped_mesh(n: int, axis: str) -> Mesh:
    return make_mesh((n,), (axis,))


def _lower_master_exchange(rows: int, n_pods: int, compression: str):
    """The fixed-delay cross-pod pop, scoped to a ('pod',) mesh — the
    DCN edge of ``ring_slot_rotate_int8_sharded`` / ``_slot_pop_sum``
    isolated from the surrounding step."""
    mesh = _scoped_mesh(n_pods, "pod")
    if compression == "int8":
        def local(q, s):     # blocks (1, rows, 128) s8, (1, rows) f32
            q_all = jax.lax.all_gather(q, "pod", axis=0, tiled=True)
            s_all = jax.lax.all_gather(s, "pod", axis=0, tiled=True)
            return jnp.sum(q_all.astype(jnp.float32) * s_all[..., None],
                           axis=0)
        args = (jax.ShapeDtypeStruct((n_pods, rows, 128), jnp.int8),
                jax.ShapeDtypeStruct((n_pods, rows), jnp.float32))
        in_specs = (P("pod", None, None), P("pod", None))
    else:
        def local(slot):     # block (1, rows, 128) f32
            return jax.lax.psum(slot[0], "pod")
        args = (jax.ShapeDtypeStruct((n_pods, rows, 128), jnp.float32),)
        in_specs = (P("pod", None, None),)
    fn = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                               out_specs=P(None, None), check_vma=False))
    return fn.lower(*args).compile()


def _lower_variable_exchange(rows: int, n_pods: int):
    """The v3 pop's single DCN reduce: one f32 psum of the locally
    folded rows (``ring_variable_pop_sharded``)."""
    mesh = _scoped_mesh(n_pods, "pod")

    def local(acc):          # block (1, rows, 128) f32: the local fold
        return jax.lax.psum(acc[0], "pod")

    fn = jax.jit(jax.shard_map(local, mesh=mesh,
                               in_specs=(P("pod", None, None),),
                               out_specs=P(None, None), check_vma=False))
    arg = jax.ShapeDtypeStruct((n_pods, rows, 128), jnp.float32)
    return fn.lower(arg).compile()


def _lower_gossip_exchange(topology: str, n_workers: int, rows: int,
                           compression: str):
    """r gossip rounds under shard_map — the same scoped program the
    gossip-bytes benchmark censuses (rounds scan once in the HLO, so
    the census is per-round)."""
    mesh = _scoped_mesh(n_workers, "worker")
    sp = P("worker", None, None)
    if compression == "int8":
        def local(x, res):
            return consensus.gossip_rounds_shard_int8(
                x, res, "worker", topology, n_workers, GOSSIP_ROUNDS)
    else:
        def local(x, res):
            return consensus.gossip_rounds_shard(
                x, "worker", topology, n_workers, GOSSIP_ROUNDS), res
    fn = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(sp, sp),
                               out_specs=(sp, sp), check_vma=False))
    arg = jax.ShapeDtypeStruct((n_workers, rows, 128), jnp.float32)
    return fn.lower(arg, arg).compile()


def _lower_publish_exchange(rows: int, n_shards: int):
    """The publish-channel pop's gather: flat-sharded s8 snapshot +
    bf16 scales to full rows on every server device, then the local
    dequantize.  The scales ride the wire as their raw u16 bits —
    the publisher's own serialization (``serve/publisher`` carries
    ``scales_bits``), and gathering the bits keeps the CPU backend
    from legalizing a bf16 all-gather by promoting the payload to
    f32 (which the census invariant flagged)."""
    from repro.optim.compression import dequantize_int8_rows
    mesh = _scoped_mesh(n_shards, "flat")

    def local(q, s_bits):    # blocks (rows/n, 128) s8, (rows/n,) u16
        q_all = jax.lax.all_gather(q, "flat", axis=0, tiled=True)
        s_all = jax.lax.all_gather(s_bits, "flat", axis=0, tiled=True)
        scales = jax.lax.bitcast_convert_type(s_all, jnp.bfloat16)
        return dequantize_int8_rows(q_all, scales)

    fn = jax.jit(jax.shard_map(local, mesh=mesh,
                               in_specs=(P("flat", None), P("flat")),
                               out_specs=P(None, None), check_vma=False))
    args = (jax.ShapeDtypeStruct((rows, 128), jnp.int8),
            jax.ShapeDtypeStruct((rows,), jnp.uint16))
    return fn.lower(*args).compile()


def _publish_shards(rows: int) -> int:
    for n in (16, 8, 4, 2):
        if rows % n == 0 and n <= len(jax.devices()):
            return n
    return 1


def lower_exchange(cell: MatrixCell, rc: RunConfig, rows: int):
    """(kind, compiled, analytic-by-dtype) for the cell's exchange
    path; (None, None, {}) when the cell has no exchange edge (a
    single-pod master cell — no DCN)."""
    if cell.strategy == "decentralized":
        compiled = _lower_gossip_exchange(
            cell.topology, cell.n_workers, rows, cell.gossip_compression)
        model = wire_model.gossip_round_bytes(
            cell.topology, cell.n_workers, rows,
            compression=cell.gossip_compression)
        return "gossip_round", compiled, model
    if cell.kind == "decode":
        n = _publish_shards(rows)
        if n <= 1:
            return None, None, {}
        return ("publish_pop", _lower_publish_exchange(rows, n),
                wire_model.publish_pop_bytes(rows, n))
    n_pods = rc.mesh.n_pods
    if n_pods <= 1:
        return None, None, {}
    if cell.delay_process != "fixed":
        return ("variable_pod_psum",
                _lower_variable_exchange(rows, n_pods),
                wire_model.variable_pod_exchange_bytes(rows, n_pods))
    return ("master_pod_exchange",
            _lower_master_exchange(rows, n_pods, cell.pod_compression),
            wire_model.master_pod_exchange_bytes(
                rows, n_pods, cell.pod_compression))


def check_exchange(cell: MatrixCell, rc: RunConfig, rows: int) -> Dict:
    kind, compiled, model = lower_exchange(cell, rc, rows)
    if kind is None:
        return {"kind": "none", "ok": True, "census": {},
                "census_by_dtype": {}, "analytic_by_dtype": {},
                "note": "single-pod master cell: no DCN edge"}
    text = compiled.as_text()
    census = collective_bytes(text, strict=True)
    by_dtype = collective_bytes_by_dtype(text, strict=True)
    # C: strict census == closed-form model, exactly, per dtype
    census_ok = by_dtype == model
    # B: compressed edges — with int8 on, everything except the
    # sanctioned scale payload must travel as s8
    compressed = (cell.gossip_compression == "int8"
                  if cell.strategy == "decentralized" else
                  cell.pod_compression == "int8"
                  or kind == "publish_pop")
    scale_dts = {"f32", "u16", "bf16"}
    if compressed:
        extra = {dt: b for dt, b in by_dtype.items()
                 if dt != "s8" and (dt not in scale_dts
                                    or b != model.get(dt))}
        compressed_ok = not extra and by_dtype.get("s8", 0) > 0
    else:
        compressed_ok = True
    return {"kind": kind, "ok": census_ok and compressed_ok,
            "census_matches_model": census_ok,
            "compressed_edges": compressed_ok if compressed else "n/a",
            "census": census, "census_by_dtype": by_dtype,
            "analytic_by_dtype": model}


# ---------------------------------------------------------------------------
# Cell driver
# ---------------------------------------------------------------------------
def run_matrix_cell(cell: MatrixCell, verbose: bool = True) -> Dict:
    if cell.devices != len(jax.devices()):
        raise RuntimeError(
            f"cell {cell.name} needs {cell.devices} devices but this "
            f"process has {len(jax.devices())} "
            f"(XLA pins the count at startup; run via "
            f"benchmarks/matrix_sweep.py or set REPRO_HOST_DEVICES)")
    rc = build_cell_rc(cell)
    rows = _arena_rows(rc)
    t0 = time.time()
    row = dryrun.run_cell(cell.arch, rc.shape.name,
                          rc.mesh.n_pods > 1, rc=rc, verbose=False,
                          want_hlo=True)
    hlo_text = row.pop("hlo_text")
    publish_hlo = None
    if "publish_pop" in row:
        publish_hlo = row["publish_pop"].pop("hlo_text", None)
    row.update({
        "cell": cell.name,
        "devices": cell.devices,
        "mesh": mesh_label(rc.mesh),
        "arena_rows": rows,
        "copy_bytes": copy_bytes(hlo_text),
        "copy_count": sum(copy_shapes(hlo_text).values()),
        "pod_compression": cell.pod_compression,
        "gossip_compression": cell.gossip_compression,
    })
    row["invariants"] = {
        "ring_copies": check_ring_copies(cell, rc, rows, hlo_text,
                                         publish_hlo),
        "exchange": check_exchange(cell, rc, rows),
    }
    row["invariants"]["ok"] = (row["invariants"]["ring_copies"]["ok"]
                               and row["invariants"]["exchange"]["ok"])
    row["cell_seconds"] = round(time.time() - t0, 1)
    if verbose:
        inv = row["invariants"]
        print(f"{cell.name}: invariants "
              f"{'OK' if inv['ok'] else 'FAILED'} "
              f"(exchange={inv['exchange']['kind']}, "
              f"{row['cell_seconds']}s)", flush=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=None,
                    help="comma-separated cell names (see --list)")
    ap.add_argument("--all", action="store_true",
                    help="every cell matching this process's device "
                         "count (others are reported as skipped)")
    ap.add_argument("--devices", type=int, default=None,
                    help="virtual device count this process was "
                         "launched for (cross-checked against the "
                         "effective XLA flag)")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    if args.list:
        for c in CELLS:
            print(f"{c.name}  devices={c.devices} strategy={c.strategy} "
                  f"arch={c.arch} mesh={c.mesh} kind={c.kind}")
        return

    if args.devices is not None and args.devices != HOST_DEVICES:
        print(f"--devices {args.devices} != effective device count "
              f"{HOST_DEVICES} (flag pinned before launch?)",
              file=sys.stderr)
        sys.exit(2)

    if args.cells:
        cells = [CELLS_BY_NAME[n] for n in args.cells.split(",")]
        bad = [c.name for c in cells if c.devices != HOST_DEVICES]
        if bad:
            print(f"cells {bad} need a different device count than "
                  f"this process's {HOST_DEVICES}", file=sys.stderr)
            sys.exit(2)
        skipped = []
    elif args.all:
        cells = [c for c in CELLS if c.devices == HOST_DEVICES]
        skipped = [c.name for c in CELLS if c.devices != HOST_DEVICES]
    else:
        print("pass --cells, --all or --list", file=sys.stderr)
        sys.exit(2)

    results, failures = [], []
    for cell in cells:
        try:
            row = run_matrix_cell(cell)
            results.append(row)
            if not row["invariants"]["ok"]:
                failures.append({"cell": cell.name,
                                 "error": "invariant violation",
                                 "invariants": row["invariants"]})
        except Exception as e:  # noqa: BLE001
            failures.append({"cell": cell.name, "error": repr(e)[:800]})
            print(f"FAIL {cell.name}: {e!r}", file=sys.stderr)
    out = {"devices": HOST_DEVICES, "results": results,
           "failures": failures, "skipped_wrong_device_count": skipped}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    n_ok = sum(1 for r in results if r["invariants"]["ok"])
    print(f"{n_ok} cells OK, {len(failures)} failed, "
          f"{len(skipped)} skipped (device count {HOST_DEVICES})")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
