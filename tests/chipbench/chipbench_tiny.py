"""A tiny cell for exercising the harness on the CPU: a data directory that
holds a small configuration, a small traffic mix and their limits beside the
benchmark's own entries, metrics and references, and a declaration with one
cell."""

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CONFIG = {
    "name": "tiny", "source": "test", "reference": "dense_decoder",
    "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "eos_token_id": 2, "prod_head": {"hidden": 32, "n_bins": 16, "bin_max": 64.0},
}
MIX = {"entry": "static_generate", "loop": "closed", "clients": 4,
       "prompt_tokens": [8, 24], "max_new": 6, "temperature": 0.8,
       "greedy_every": 2, "check_requests": 2, "quantiles": [0.5, 0.9, 0.95]}
CELL = "tiny.chat"
# bf16 program against a float32 reference at this size: ~3e-3 logits, ~1e-2
# relative in φ, ~2e-7 in CDF; the fp8 control reads ~0.35, ~0.17 and ~1e-3
LIMITS = {"token_logit_gap": 0.1, "phi_gap": 0.05, "head_cdf_gap": 1e-4}


def make(tmp: Path, config=CONFIG, mix=MIX, cell=CELL, limits=LIMITS) -> tuple:
    """(data directory, declaration path) for one tiny cell under ``tmp``."""
    data = tmp / "data"
    for kind in ("entries", "metrics", "references"):
        shutil.copytree(ROOT / "chipbench" / kind, data / kind,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for kind, name, obj in (("configs", config["name"], config),
                            ("traffic", cell.split(".", 1)[1], mix),
                            ("limits", cell, limits)):
        (data / kind).mkdir(parents=True, exist_ok=True)
        (data / kind / f"{name}.json").write_text(json.dumps(obj))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"] = [{"name": cell, "config": config["name"],
                          "traffic": cell.split(".", 1)[1], "chips": 1, "why": "test"}]
    path = tmp / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return data, path
