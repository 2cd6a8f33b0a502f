"""What a cell is: its entry in BENCHMARK.json, its configuration file and
its traffic file, each found by name. Nothing here knows a cell by name."""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

# published config.json key -> the program's ArchConfig field
ARCH_FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "torch_dtype": "dtype",
    "qkv_bias": "qkv_bias",
    "qk_norm": "qk_norm",
}


@dataclass(frozen=True)
class Dims:
    """The sizes of a dense decoder LM that the yardstick needs (FLOPs,
    bytes, weights, the reference), read from a configuration file."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    qkv_bias: bool
    qk_norm: bool
    rope_theta: float
    eps: float
    dtype: str

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim")
                   or c["hidden_size"] // c["num_attention_heads"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   tied=bool(c["tie_word_embeddings"]),
                   qkv_bias=bool(c["qkv_bias"]), qk_norm=bool(c["qk_norm"]),
                   rope_theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]), dtype=c["torch_dtype"])

    @property
    def padded_vocab(self) -> int:
        """Rows of the embedding table the program holds (vocab rounded up
        to 256); the rows past ``vocab`` are never a token."""
        return -(-self.vocab // 256) * 256


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config_file(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(root / c["file"])
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_file(name: str) -> dict:
    return load_json(BENCH_DIR / "traffic" / f"{name}.json")


def cell_metrics(bench: dict, cell_name: str, kind: str) -> list[dict]:
    """The metrics of ``kind`` (``end_to_end`` or ``per_layer``) that this
    cell reports: those that list it, and those that list no cells."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def arch_config(config: dict):
    """The program's ArchConfig for a configuration file: the registry entry
    named by ``arch``, with every published key of the file applied."""
    from repro.configs import get_config

    fields = {ARCH_FIELDS[k]: v for k, v in config.items()
              if k in ARCH_FIELDS}
    return get_config(config["arch"]).replace(**fields)
