"""Helpers of the benchmark's CPU tests: a checkout-like directory that
holds one tiny cell, at the program's smoke widths, made only of files
and manifest entries as a later cell would be."""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# the program's smoke widths (repro.configs.<arch>.smoke_config)
CONFIGS = {
    "dense": {"name": "tiny-dense", "family": "dense",
              "arch": "internlm2_1_8b", "num_hidden_layers": 2,
              "hidden_size": 128, "intermediate_size": 256,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 32, "vocab_size": 512, "rope_theta": 1e6},
    "ssm": {"name": "tiny-ssm", "family": "ssm", "arch": "mamba2_370m",
            "n_layer": 2, "d_model": 128, "vocab_size": 512, "d_state": 32,
            "d_conv": 4, "expand": 2, "headdim": 32},
}
TRAFFIC = {"dense": "local-1x2048", "ssm": "local-8x2048",
           "ring": "matcha-ring4-cb0.5"}
# limits of the tiny cells, set from CPU readings at these sizes on the
# tests' seed and five more: the program's worst and the float8
# control's least are in PERF.md, section 4
LIMITS = {
    "dense": {"loss_gap": 5e-4, "grad_gap": 8e-3, "change_gap": 8e-3},
    "ssm": {"loss_gap": 5e-4, "grad_gap": 5e-2, "change_gap": 3e-2},
    "ring": {"loss_gap": 5e-4, "grad_gap": 8e-3, "change_gap": 8e-3},
}


def make_root(tmp: Path, kind: str, *, batch: int = 2, seq: int = 64):
    """A directory with ``BENCHMARK.json`` and ``bench/{configs,traffic,
    limits}`` for one cell named ``tiny``; returns its bench dir."""
    bench = tmp / "bench"
    for d in ("configs", "traffic", "limits"):
        (bench / d).mkdir(parents=True, exist_ok=True)
    config = CONFIGS["dense" if kind == "ring" else kind]
    traffic = json.loads(
        (REPO / "bench" / "traffic" / f"{TRAFFIC[kind]}.json").read_text())
    traffic.update(name="tiny-mix", batch_per_node=batch, seq=seq)
    (bench / "configs" / "tiny.json").write_text(json.dumps(config))
    (bench / "traffic" / "tiny-mix.json").write_text(json.dumps(traffic))
    (bench / "limits" / "tiny.json").write_text(
        json.dumps({"limits": LIMITS[kind]}))
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny", "source": "smoke widths", "file":
        "bench/configs/tiny.json", "reduced": [], "why": "CPU test"})
    manifest["workloads"].append({
        "name": "tiny", "config": "tiny", "traffic": "tiny-mix",
        "chips": int(traffic["nodes"]), "why": "CPU test"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    return bench


def smoke_model_config(config: dict):
    from bench import program
    from repro.configs.registry import get_smoke_config

    return dataclasses.replace(get_smoke_config(config["arch"]),
                               num_layers=program.layers_of(config))
