"""A profiler trace of training, summed by the names the program gives
its work (docs/profiling.md).

    python -m repro.train.profile <trace dir or .xplane.pb>

prints one JSON object: the device time of an average step by pass
(forward, backward, recomputation), by model part and by master part,
what no scope covers, and the host loop's span time a step. Steps are
the executions of the jitted ``train_step`` that start in the trace.

A device op's ``op_name`` is the stat ``tf_op`` of its event metadata
in the ``.xplane.pb`` (an op the profiler deduplicated names its twin
instead). ``jax.profiler.ProfileData`` shows events and their own stats
but not their metadata's, so this module reads the file's protobuf wire
format itself.
"""
from __future__ import annotations

import collections
import glob
import json
import re
import sys
from typing import Dict, List, NamedTuple, Tuple

OP_NAME_STAT = "tf_op"
STEP_MODULE = re.compile(r"train_step")
MODEL_PARTS = ("model.embed", "model.attn", "model.mixer", "model.mlp",
               "model.moe", "model.head")
HOST_PREP = ("loop.batch", "loop.draws", "loop.put")


# ---------------------------------------------------------------------------
# Reading the .xplane.pb: the XSpace protobuf's wire format, the fields
# used here only (tsl/profiler/protobuf/xplane.proto)
# ---------------------------------------------------------------------------
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """(field number, value) of one message: ints for varints, bytes
    for length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield key >> 3, value


def _map_values(entries: List[bytes]):
    """A protobuf map's values (entry: key 1, value 2)."""
    for entry in entries:
        yield dict(_fields(entry)).get(2, b"")


def _stat_value(stat: Dict[int, object], names: Dict[int, str]):
    # XStat: metadata_id 1, str_value 5, ref_value 7 (interned string)
    if 5 in stat:
        return stat[5].decode()
    if 7 in stat:
        return names.get(stat[7], "")
    return None


class Op(NamedTuple):
    device: str
    name: str
    op_name: str
    start_ns: float
    dur_ns: float


def _plane(buf: bytes):
    """(name, event metadata id -> (name, op_name), lines); each line
    (name, [(metadata id, start_ns, dur_ns)])."""
    # XPlane: name 2, lines 3, event_metadata 4, stat_metadata 5
    fields = collections.defaultdict(list)
    for f, v in _fields(buf):
        fields[f].append(v)
    name = fields[2][0].decode() if fields[2] else ""
    stat_names = {}
    for value in _map_values(fields[5]):
        meta = dict(_fields(value))          # XStatMetadata: id 1, name 2
        stat_names[meta.get(1, 0)] = meta.get(2, b"").decode()
    metas, dedup, by_short = {}, {}, {}
    for value in _map_values(fields[4]):
        # XEventMetadata: id 1, name 2, stats 5
        mid, text, op_name, twin = 0, "", "", None
        for g, v in _fields(value):
            if g == 1:
                mid = v
            elif g == 2:
                text = v.decode()
            elif g == 5:
                stat = dict(_fields(v))
                key = stat_names.get(stat.get(1))
                if key == OP_NAME_STAT:
                    op_name = _stat_value(stat, stat_names) or ""
                elif key == "deduplicated_name":
                    twin = _stat_value(stat, stat_names)
        short = text.split(" = ", 1)[0].lstrip("%")
        if op_name:
            by_short[short] = op_name
        elif twin:
            dedup[mid] = twin
        metas[mid] = (text, op_name)
    # an op the profiler deduplicated carries its twin's op_name
    for mid, twin in dedup.items():
        metas[mid] = (metas[mid][0], by_short.get(twin, ""))
    lines = []
    for line in fields[3]:
        # XLine: name 2, timestamp_ns 3, events 4; XEvent: metadata_id 1,
        # offset_ps 2, duration_ps 3
        lname, t0, events = "", 0, []
        for g, v in _fields(line):
            if g == 2:
                lname = v.decode()
            elif g == 3:
                t0 = v
            elif g == 4:
                e = dict(_fields(v))
                events.append((e.get(1, 0), e.get(2, 0), e.get(3, 0)))
        lines.append((lname, [(m, t0 + off / 1e3, dur / 1e3)
                              for m, off, dur in events]))
    return name, metas, lines


# ---------------------------------------------------------------------------
# Events
# ---------------------------------------------------------------------------
def find_xplane(path: str) -> str:
    if path.endswith(".xplane.pb"):
        return path
    found = sorted(glob.glob(f"{path}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load(path: str):
    """(leaf device ops, step module executions, host loop spans) of one
    trace; modules and spans as (name, start_ns, dur_ns)."""
    with open(path, "rb") as f:
        raw = f.read()
    ops: List[Op] = []
    modules, spans = [], []
    for f, buf in _fields(raw):
        if f != 1:                           # XSpace: planes 1
            continue
        plane, metas, lines = _plane(buf)
        for lname, events in lines:
            named = [(metas.get(m, ("", ""))) + (s, d) for m, s, d in events]
            if plane.startswith("/host:"):
                spans += [(n, s, d) for n, _, s, d in named
                          if n.startswith("loop.")]
            elif lname == "XLA Ops":
                ops += [Op(plane, n, o, s, d) for n, o, s, d in named]
            elif lname == "XLA Modules":
                modules += [(n, s, d) for n, _, s, d in named
                            if STEP_MODULE.search(n)]
    return _leaves(ops), modules, spans


def _leaves(ops: List[Op]) -> List[Op]:
    """Ops that contain no other op of their device (a loop's or a
    call's own event spans the ops inside it)."""
    out = []
    by_dev = collections.defaultdict(list)
    for op in ops:
        by_dev[op.device].append(op)
    for dev_ops in by_dev.values():
        dev_ops.sort(key=lambda o: (o.start_ns, -o.dur_ns))
        for a, b in zip(dev_ops, dev_ops[1:] + [None]):
            if b is None or not (b.start_ns < a.start_ns + a.dur_ns
                                 and b.start_ns + b.dur_ns
                                 <= a.start_ns + a.dur_ns):
                out.append(a)
    return out


# ---------------------------------------------------------------------------
# Attribution
# ---------------------------------------------------------------------------
def parts(op_name: str) -> List[str]:
    """The parts one op counts toward. A pass (``forward``, ``backward``,
    ``recompute``) from JAX's markers around ``worker.forward``; the
    innermost model scope under it, and inside ``model.moe`` its
    innermost ``moe.*`` scope too; ``master`` and its innermost
    ``master.*`` scope; ``worker.accumulate``; else ``unscoped``."""
    out = []
    if "worker.forward" in op_name:
        if "rematted_computation" in op_name:
            out.append("recompute")
        elif "transpose(" in op_name:
            out.append("backward")
        else:
            out.append("forward")
        model = [p for p in MODEL_PARTS if p in op_name]
        if model:
            out.append(max(model, key=op_name.rfind))
        if out[-1] == "model.moe":
            out += re.findall(r"\bmoe\.\w+", op_name)[-1:]
    elif "master." in op_name:
        out += ["master", re.findall(r"master\.\w+", op_name)[-1]]
    elif "worker.accumulate" in op_name:
        out.append("worker.accumulate")
    else:
        out.append("unscoped")
    return out


def summarize(ops: List[Op], modules, spans) -> Dict:
    """Per step of the trace, in ms: device time by part, the step
    module's own time, and host time by loop span (``host_prep`` is the
    union of the batch, draws and put spans)."""
    if not modules:
        raise ValueError("the trace holds no train_step execution")
    lo = min(s for _, s, _ in modules)
    hi = max(s + d for _, s, d in modules)
    n_dev = len({op.device for op in ops}) or 1
    steps = len(modules) / n_dev         # each device runs every step
    device = collections.defaultdict(float)
    for op in ops:
        if lo <= op.start_ns < hi:
            for p in parts(op.op_name):
                device[p] += op.dur_ns
    host = collections.defaultdict(float)
    prep = []
    for name, start, dur in spans:
        if lo <= start < hi:
            host[name] += dur
            if name in HOST_PREP:
                prep.append((start, start + dur))
    merged: List[List[float]] = []
    for s, t in sorted(prep):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    host["host_prep"] = sum(t - s for s, t in merged)

    def per_step(d, scale):
        return {k: v / scale / steps / 1e6 for k, v in sorted(d.items())}
    return {"steps": steps,
            "step_ms": sum(d for _, _, d in modules) / len(modules) / 1e6,
            "device_ms": per_step(device, n_dev),
            "host_ms": per_step(host, 1)}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python -m repro.train.profile <trace dir or "
              ".xplane.pb>", file=sys.stderr)
        return 2
    print(json.dumps(summarize(*load(find_xplane(argv[0]))), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
