"""FLOP counts of the GRU step against a hand count; a kernel's roofline share."""

from __future__ import annotations

import os
import shutil

import pytest

from bench_testutil import BENCH, REPO, read_json

import harness
from files import Bench

MS = 1_000_000


def test_step_flops_per_example_hand_count():
    # Paper shapes: 24 hours, input 38, hidden 32, 2 layers, head 32 -> 1.
    # layer 0: 24 * 2 * 96 * (38 + 32) = 322,560; layer 1: 24 * 2 * 96 * 64
    # = 294,912; head 2 * 32 = 64; forward 617,536; training = 3x forward.
    cfg = read_json(os.path.join(REPO, "bench", "configs", "gru-eicu-xla.json"))
    flops = Bench(REPO).kernel("gru_step").flops_per_example(cfg)
    assert flops == 3 * (322_560 + 294_912 + 64) == 1_852_608


TOY_KERNEL = '''
DETAIL_PATTERN = r"toy_call"


def matches(config, detail):
    return "toy_call" in detail


def cost(config, detail):
    rows = int(detail.split("[")[1].split("]")[0])
    return {"flops": rows * 1_000_000, "bytes": rows * 1_000}
'''


@pytest.mark.parametrize(
    "rows, share",
    [(100, 5.0), (1000, 50.0)],  # 1e8 or 1e9 FLOPs per 2 ms call at 1e12 FLOP/s
)
def test_kernel_file_gives_its_roofline_share(tmp_path, rows, share):
    """A kernel's cost file, added as a file only, is read against the trace."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(BENCH, bench_dir, ignore=shutil.ignore_patterns("__pycache__"))
    (bench_dir / "kernels" / "toy.py").write_text(TOY_KERNEL)
    bench = Bench(REPO, bench_dir=str(bench_dir))
    assert "toy" in bench.kernel_names()
    events = {
        "host": [("bench.window.open", 0, 10, ""), ("bench.window.close", 10 * MS, 10 * MS + 10, "")],
        "device": {"0": [
            ("toy.1", 1 * MS, 3 * MS, f"%toy = f32[{rows}] toy_call()"),
            ("fusion.1", 3 * MS, 4 * MS, ""),
            ("toy.1", 5 * MS, 7 * MS, f"%toy = f32[{rows}] toy_call()"),
            ("toy.1", 9 * MS, 11 * MS, f"%toy = f32[{rows}] toy_call()"),  # past the close
        ]},
    }
    run = harness.TracedRun(
        bench=bench, cell=bench.cell("gru-xla.ac"),
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        events=events, spans=[], window_rounds=[], examples=0, window_compiles=0,
        window_s=0.01, busy_s=0.005,
    )
    assert run.roofline("toy") == pytest.approx(share)
