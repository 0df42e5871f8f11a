"""A benchmark cell cut to a size the CPU test run can hold: the same
harness, traffic generator, reference and comparison as the chip cells,
with a 2-layer model of the configuration's kind (its family's ``tiny``),
run in float32.

At this size the bfloat16 program's widest gap (0.04-0.27 over windows of
0.6-3 s) comes within reach of the float8 control's (0.36 and up), so no
limit would separate them on every window; in float32 the program reads
0.0, the reference's own picks, and the control 0.15 and up (0.41 and up
on chat, where more tokens are compared)."""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run as R  # noqa: E402

# between the float32 program's 0.0 and the float8 control's 0.15 and up
TINY_LIMIT = 0.05


def tiny_config(config: str = "dense-gqa-2b") -> dict:
    """The configuration file cut to test size by its family's ``tiny``,
    in float32, with a 4-slot pool of 128-token contexts."""
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                     .read_text())
    cfg = R.load_family(cfg).plain.tiny(cfg)
    cfg["torch_dtype"] = "float32"
    cfg["serving"] = dict(cfg["serving"], max_slots=4, max_context=128,
                          n_pages=33)
    return cfg


def tiny_cell(config: str = "dense-gqa-2b", mix: str = "chat",
              limit: float = TINY_LIMIT) -> R.Cell:
    cfg = tiny_config(config)
    m = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json").read_text())
    if m["loop"] == "closed":
        # the mix's own backlog of 256: an idle CPU serves 64 of these
        # requests in under the 3 s the longest test window lasts
        m.update(prompt_tokens={"median": 40, "sigma": 0.5, "min": 8,
                                "max": 80},
                 output_tokens={"median": 12, "sigma": 0.5, "min": 4,
                                "max": 40})
    else:
        m.update(prompt_tokens={"median": 40, "sigma": 0.5, "min": 8,
                                "max": 120}, rate_per_s=4.0)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = f"dense-gqa-2b.{mix}"
    e2e = R._for_cell(spec["end_to_end"], name)
    if mix == "score":
        # the open-loop cell is out of BENCHMARK.json for now (PERF.md,
        # section 7); its harness path keeps its metric here
        e2e.append({"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
                    "source": "host_clock"})
    return R.Cell(name=name, chips=1, config=cfg,
                  family=R.load_family(cfg), mix=m,
                  limits={"widest_logit_gap": limit, "min_tokens": 1,
                          "sample_requests": 4},
                  end_to_end=e2e,
                  per_layer=R._for_cell(spec["per_layer"], name,
                                        {e["name"] for e in e2e}))


def run_tiny(cell: R.Cell, seed: int, seconds: float = 1.5,
             trace: bool = False, control: bool = False) -> dict:
    """``bench.run.run`` on the CPU, from JAX's caches as a fresh process
    has them, as every run on the chip starts.

    A test run's worker process carries what earlier test files left in
    JAX's dispatch caches.  After ``tests/test_models.py``'s
    recurrentgemma init, each eager ``jax.random.fold_in`` of the
    scheduler's step counter goes back through Python dispatch and
    records a ``jaxpr_trace_duration`` event (nothing is lowered or
    compiled), so the window's ``CompileWatch`` refuses the run; the
    caches are cleared first."""
    import jax
    jax.clear_caches()
    peak = json.loads((ROOT / "bench" / "peaks.json").read_text())[
        "TPU v5 lite"]
    return R.run(cell, seed, seconds, trace,
                 device=R.device_record(jax.devices()), peak=peak,
                 control=control)
