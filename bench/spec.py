"""Cells of the benchmark, read from ``BENCHMARK.json`` and the data files
it names.

A cell is one entry of ``workloads``: a configuration (``configs/<name>.json``,
the served models with their published sizes and the chips they run on)
under a traffic mix (``traffic/<name>.json``, the DAG, the lengths and the
arrival process).  Everything is found by name, so a new configuration, mix
or per-layer metric is a new file plus an entry in ``BENCHMARK.json``.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """One served model: its alias in the planner's profiles and its
    published ``config.json`` as it is run."""
    alias: str
    seed_offset: int
    arch: str                 # architecture name in the program
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    tied: bool
    qk_norm: bool
    qkv_bias: bool
    dtype: str

    @classmethod
    def from_json(cls, entry: dict) -> "ModelSpec":
        hf = entry["config"]
        arch = hf["architectures"][0]
        if arch not in ("Qwen3ForCausalLM", "Qwen2ForCausalLM"):
            raise ValueError(f"no plain reference for {arch}")
        heads = hf["num_attention_heads"]
        return cls(
            alias=entry["alias"], seed_offset=int(entry["seed_offset"]),
            arch=entry["arch"], layers=hf["num_hidden_layers"],
            d_model=hf["hidden_size"], heads=heads,
            kv_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
            d_ff=hf["intermediate_size"], vocab=hf["vocab_size"],
            rope_theta=float(hf["rope_theta"]),
            norm_eps=float(hf["rms_norm_eps"]),
            tied=bool(hf["tie_word_embeddings"]),
            # Qwen3 normalises q and k per head; Qwen2 (Qwen1.5) has a
            # bias on the q, k and v projections
            qk_norm=arch == "Qwen3ForCausalLM",
            qkv_bias=(arch == "Qwen2ForCausalLM"
                      or bool(hf.get("attention_bias", False))),
            dtype=hf["torch_dtype"])

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * 2


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    models: tuple[ModelSpec, ...]
    end_to_end: tuple[str, ...]
    per_layer: tuple[dict, ...]      # BENCHMARK.json entries for this cell

    @property
    def n_devices(self) -> int:
        return self.chips * self.config["virtual_devices_per_chip"]


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def for_cell(entries: list, cell: str) -> tuple[dict, ...]:
    """Metric entries that a cell reports: those without ``workloads``,
    and those that list it."""
    return tuple(m for m in entries
                 if "workloads" not in m or cell in m["workloads"])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``; raises ``KeyError``
    for a name that is not there."""
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = read_json(root / cfg_entry["file"])
    traffic = read_json(BENCH / "traffic" / f"{w['traffic']}.json")
    if config["chips"] != w["chips"]:
        raise ValueError(f"{name}: BENCHMARK.json asks for {w['chips']} "
                         f"chips, {cfg_entry['file']} for {config['chips']}")
    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                models=tuple(ModelSpec.from_json(m)
                             for m in config["models"]),
                end_to_end=tuple(m["name"] for m in
                                 for_cell(bench["end_to_end"], name)),
                per_layer=for_cell(bench["per_layer"], name))
