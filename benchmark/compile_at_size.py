"""Compile a training cell's step at its real size for a described v5e
topology, without a chip: what the TPU compiler refuses or cannot fit is
heard here and costs no chip time.

``JAX_PLATFORMS=cpu python3 benchmark/compile_at_size.py --workload train_gpt2xl_fsdp4``
prints the per-device memory analysis, the collectives the compiler put in
and whether the flash kernel is there. Nothing runs; no time, rate or
utilisation can come from this.
"""

from __future__ import annotations

import argparse
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sequences_per_chip", type=int, default=None)
    ap.add_argument("--remat", default=None)
    args = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies

    from harness import program, registry
    from pretraining_llm_tpu.parallel.mesh import build_mesh
    from pretraining_llm_tpu.training import train_step as ts

    jax.config.update("jax_enable_compilation_cache", False)
    cell = registry.cell(args.workload)
    arch = registry.load_config(cell["config"])
    traffic = registry.load_traffic(cell["traffic"])
    if args.sequences_per_chip:
        traffic["job"]["sequences_per_chip"] = args.sequences_per_chip
    if args.remat:
        traffic["job"].setdefault("model", {})["remat"] = args.remat
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = list(topo.devices)[: cell["chips"]]
    cfg, mesh = program.train_config(arch, traffic, devices, 0)
    if mesh is None:  # one chip: a mesh of one described device, so that the TPU compiler is the target
        mesh = build_mesh(cfg.mesh, devices)
    compiled = ts.lower_train_step(cfg, mesh).compile()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    gb = 1e9
    print(f"{cell['name']}: batch {cfg.train.batch_size} x {cfg.model.context_length}, remat {cfg.model.remat}, "
          f"mesh {dict(mesh.shape)}")
    print(f"per device: arguments {m.argument_size_in_bytes / gb:.2f} GB, temporaries "
          f"{m.temp_size_in_bytes / gb:.2f} GB, outputs {m.output_size_in_bytes / gb:.2f} GB "
          f"(aliased {m.alias_size_in_bytes / gb:.2f} GB)")
    for op in ("all-gather", "all-reduce", "reduce-scatter", "collective-permute", "all-to-all"):
        print(f"{op}: {len(re.findall(rf' {op}(?:-start)?\(', text))}")
    targets = re.findall(r'custom_call_target="([^"]+)"', text)
    print('custom calls by target:', {t: targets.count(t) for t in sorted(set(targets))})
    return 0


if __name__ == "__main__":
    sys.exit(main())
