"""A copy of the benchmark's tree with its cells cut to CPU size, for tests:
the same drivers, traffic and readers, on tiny configurations.  A cell whose
numbers read differently at tiny size has its limits here, set from tiny
readings as the cell's own are set from readings at its size."""
from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]

TINY_CONFIGS = {
    "kron-gp-16x16e6": {"m": 8, "ps": [4, 4, 4], "qs": [4, 4, 4]},
    "kron-dist-64e4": {"m": 8, "ps": [4, 4, 4], "qs": [4, 4, 4]},
    "qwen3-4b-kronffn": {"hidden_size": 64, "intermediate_size": 96,
                         "num_attention_heads": 4, "num_key_value_heads": 2,
                         "head_dim": 16, "vocab_size": 256},
}
TINY_TRAFFIC = {"train_b2_s2048": {"batch": 2, "seq": 16, "token_pool": 8}}
# CPU at the tiny size, 12 seeds: sound runs read loss_gap up to 2.2e-4,
# grad_norm_gap 1.1e-2, median_grad_norm_gap 2.5e-3, change_norm_gap 4.1e-2;
# the fp8 control at least 8.3e-5, 3.5e-2, 1.1e-2, 3.5e-2; half a batch at
# least 3.4e-3, 0.12, 3.2e-2, 0.15.
TINY_LIMITS = {"qwen3-4b-train": {"loss_gap": 8e-4,
                                  "grad_norm_gap": 2e-2, "median_grad_norm_gap": 5e-3,
                                  "change_norm_gap": 0.3}}


def make_root(dest: pathlib.Path) -> pathlib.Path:
    """``dest`` laid out as a checkout: ``BENCHMARK.json`` and ``bench/``
    (configs cut to tiny sizes, the rest copied)."""
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench = dest / "bench"
    for sub in ("traffic", "limits", "metrics"):
        shutil.copytree(BENCH / sub, bench / sub)
    (bench / "configs").mkdir(parents=True)
    for c in spec["configs"]:
        cfg = json.loads((BENCH.parent / c["file"]).read_text())
        cfg.update(TINY_CONFIGS.get(c["name"], {}))
        (dest / c["file"]).write_text(json.dumps(cfg))
    for name, over in TINY_TRAFFIC.items():
        path = bench / "traffic" / f"{name}.json"
        path.write_text(json.dumps({**json.loads(path.read_text()), **over}))
    for name, limits in TINY_LIMITS.items():
        (bench / "limits" / f"{name}.json").write_text(json.dumps(limits))
    (dest / "BENCHMARK.json").write_text(json.dumps(spec))
    return dest
