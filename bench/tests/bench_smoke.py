"""Smoke-size cells for the CPU tests: the benchmark's own cells with
their widths cut to a few dozen (tests only; the benchmark runs the
files as they are)."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import spec  # noqa: E402

SEQ_LEN = 128
SMOKE = {
    "dense": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                  head_dim=16, d_ff=96, vocab_size=256),
    "hybrid": dict(n_layers=2, shared_attn_every=2, d_model=64, n_heads=4,
                   n_kv_heads=4, head_dim=16, d_ff=128, vocab_size=256,
                   ssm={"d_state": 16, "d_conv": 4, "expand": 2,
                        "head_dim": 16, "chunk_size": 32, "n_groups": 1}),
}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def smoke_cell(workload: str) -> spec.Cell:
    cell = spec.load_cell(workload)
    cell.config.update(SMOKE[cell.config["reference"]])
    cell.traffic["seq_len"] = SEQ_LEN
    return cell
