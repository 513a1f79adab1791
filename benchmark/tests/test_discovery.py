"""A configuration, an architecture family, a traffic mix, a driver, a reader
and a per-layer metric are each added as new files plus entries in
BENCHMARK.json; no existing file is edited."""

import json
import os
import shutil
import subprocess
import sys

from harness import registry


def test_add_one_of_each(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(registry.BENCH_DIR, root / "benchmark", ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(registry.ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    b = root / "benchmark"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    # a new architecture family: its module, its reference, a configuration that names it
    cfg = json.loads((b / "tests" / "toy" / "gpt2.json").read_text())
    cfg.update(family="newfam", n_inner_factor=2)
    (b / "configs" / "new-model.json").write_text(json.dumps(cfg))
    fam = (b / "harness" / "families" / "gpt2.py").read_text()
    assert "ffn=4 * d" in fam
    (b / "harness" / "families" / "newfam.py").write_text(fam.replace("ffn=4 * d", "ffn=arch['n_inner_factor'] * d"))
    shutil.copy(b / "references" / "gpt2.py", b / "references" / "newfam.py")
    (b / "traffic" / "new_mix.json").write_text(json.dumps({"kind": "new_kind", "seconds": 1}))
    (b / "harness" / "drivers" / "new_kind.py").write_text("def run(ctx):\n    return 'ran new_kind'\n")
    (b / "readers" / "new_reader.py").write_text("def read(result, summary, ctx):\n    return 1.0\n")
    (b / "layer_metrics" / "new_metric.new.json").write_text(json.dumps({"reader": "new_reader"}))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "new-model", "source": "https://example.org/new", "reduced": [],
                           "file": "benchmark/configs/new-model.json", "why": "test"})
    man["workloads"].append({"name": "new_cell", "config": "new-model", "traffic": "new_mix",
                             "chips": 1, "why": "test"})
    man["end_to_end"].append({"name": "new_rate", "unit": "ops/s", "better": "higher", "bound": 0.01,
                              "source": "host_clock", "workloads": ["new_cell"]})
    man["per_layer"].append({"name": "new_metric.new", "unit": "count", "better": "higher",
                             "source": "program_counter", "layer": "device", "moves": "new_rate",
                             "workloads": ["new_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    code = (
        "import json, sys; sys.path.insert(0, 'benchmark');"
        "import jax, jax.numpy as jnp;"
        "from harness import opcount, program, registry as r, weights;"
        "c = r.cell('new_cell'); t = r.load_traffic(c['traffic']); a = r.load_config(c['config']);"
        "tree = jax.eval_shape(lambda k: weights.program_params(a, k, jnp.float32), weights.seed_key(1));"
        "lm = r.layer_metric_spec('new_metric.new');"
        "print(json.dumps({'all': r.list_all(), 'family': a['family'], 'ffn': opcount.dims(a)['ffn'],"
        " 'd_ff': program.model_config(a, 32).d_ff, 'w1': list(tree['blocks']['mlp']['w1'].shape),"
        " 'params': opcount.num_params(a),"
        " 'ran': r.driver(t['kind'])(None), 'read': r.reader(lm['reader'])(None, None, None),"
        " 'listed': [m['name'] for m in r.metrics_for('new_cell', trace=True)]}))"
    )
    # the copy holds the benchmark alone; the program (for ModelConfig) is found in the repo
    env = dict(os.environ, PYTHONPATH=registry.ROOT, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], cwd=root, capture_output=True, text=True, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    seen = json.loads(out.stdout.strip().splitlines()[-1])
    assert "new_cell" in seen["all"]["workloads"] and "new-model" in seen["all"]["configs"]
    assert "new_mix" in seen["all"]["traffic"] and "new_kind" in seen["all"]["drivers"]
    assert "new_reader" in seen["all"]["readers"] and "new_metric.new" in seen["all"]["layer_metrics"]
    assert "newfam" in seen["all"]["families"] and "newfam" in seen["all"]["references"]
    assert seen["family"] == "newfam" and seen["ran"] == "ran new_kind" and seen["read"] == 1.0
    # the new family's own arithmetic reached the counts, the weights and the program's configuration
    d, layers = cfg["n_embd"], cfg["n_layer"]
    assert seen["ffn"] == seen["d_ff"] == 2 * d and seen["w1"] == [layers, d, 2 * d]
    assert seen["params"] == layers * (4 * d * d + 4 * d + 2 * d * 2 * d + 3 * d + 4 * d) + (
        cfg["padded_vocab_size"] + cfg["n_positions"] + 2) * d
    assert seen["listed"] == ["new_metric.new"]
    assert all(p.read_bytes() == data for p, data in before.items()), "an existing file was edited"


def test_every_listed_metric_has_its_files():
    man = registry.manifest()
    for m in man["per_layer"]:
        spec = registry.layer_metric_spec(m["name"])
        assert callable(registry.reader(spec["reader"]))
        assert set(spec) <= {"reader", "args"}  # unit, layer, moves live in BENCHMARK.json alone
    for w in man["workloads"]:
        assert callable(registry.driver(registry.load_traffic(w["traffic"])["kind"]))
        registry.load_config(w["config"])
