"""The harness finds configurations, mixes and metrics by name, so a new
cell is new files and manifest entries, with no edit to a file that is
there; and a run off the chip fails with no result."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.core import manifest


def _digest(root: Path):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_new_cell_is_found_by_name_from_new_files_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces",
                                                  "__pycache__"))
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = _digest(root)

    cfg = json.loads((root / "bench/configs/ling-lite-1chip.json")
                     .read_text())
    cfg["name"] = "other-model"
    (root / "bench/configs/other-model.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/other-mix.json").write_text(json.dumps(
        {"driver": "serve", "loop": "closed", "clients": 8}))
    (root / "bench/metrics/other_metric.other.py").write_text(
        "def read(run):\n    return 42.0\n")
    man["configs"].append({"name": "other-model", "source": "x",
                           "file": "bench/configs/other-model.json",
                           "reduced": [], "why": "x"})
    man["workloads"].append({"name": "other-model.other-mix",
                             "config": "other-model", "traffic": "other-mix",
                             "chips": 1, "why": "x"})
    man["per_layer"].append({"name": "other_metric.other", "unit": "%",
                             "better": "higher", "source": "device_trace",
                             "layer": "device", "moves": "output_tok_s",
                             "workloads": ["other-model.other-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))

    cell = manifest.resolve("other-model.other-mix", root)
    assert cell.config["name"] == "other-model"
    assert cell.traffic["clients"] == 8
    assert [m["name"] for m in cell.per_layer] == ["other_metric.other"]
    assert manifest.metric_reader("other_metric.other", root)(None) == 42.0
    assert hasattr(manifest.driver(cell.traffic["driver"], root), "run")
    old = manifest.resolve(man["workloads"][0]["name"], root)
    assert "other_metric.other" not in [m["name"] for m in old.per_layer]
    after = _digest(root)
    assert {p: h for p, h in after.items() if p in before} == before


def test_every_manifest_entry_resolves_to_its_files():
    man = manifest.load_manifest()
    for w in man["workloads"]:
        cell = manifest.resolve(w["name"])
        assert cell.config["engine"]["max_context"] >= 1
        for m in cell.per_layer + cell.end_to_end:
            if m["name"] != "setup_s":
                assert callable(manifest.metric_reader(m["name"]))
        assert cell.per_layer and len(cell.end_to_end) >= 2


def test_a_run_off_the_chip_exits_non_zero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    name = manifest.load_manifest()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", name,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip()
