"""The one place that talks to the program's constructors.

From the program the benchmark takes the system under test only: its
configuration classes, ``ServingEngine``, ``EngineLoop`` and the train step.
Every option a configuration or traffic file does not name stays at the
program's default, so a PR that changes a default is measured.
"""

from __future__ import annotations

from typing import Any, Dict

from harness import families, opcount


def model_config(arch: Dict[str, Any], context_length: int, **extra: Any):
    from pretraining_llm_tpu.config import ModelConfig

    m = opcount.dims(arch)
    kw: Dict[str, Any] = dict(
        vocab_size=m["vocab_rows"], context_length=context_length, d_model=m["d"],
        n_heads=m["heads"], n_layers=m["layers"], use_output_proj=True,
    )
    kw.update(families.of(arch).model_kwargs(arch, m))
    kw.update(arch.get("program_model", {}))
    kw.update(extra)
    cfg = ModelConfig(**kw)
    if cfg.d_ff != m["ffn"] or cfg.head_dim != m["head_dim"]:
        raise ValueError("program widths differ from the configuration's")
    return cfg


def serving_engine(params: Any, cfg: Any, traffic: Dict[str, Any]):
    """``ServingEngine`` with the sizes the traffic file needs and nothing else."""
    from pretraining_llm_tpu.generation.serving import ServingEngine

    return ServingEngine(params, cfg, **traffic["engine"])


def train_config(arch: Dict[str, Any], traffic: Dict[str, Any], devices: Any, seed: int):
    """(Config, mesh over ``devices`` or None) for the train step, as the trainer builds them."""
    from pretraining_llm_tpu.config import Config, MeshConfig, TrainConfig
    from pretraining_llm_tpu.parallel.mesh import build_mesh, needs_mesh

    job = dict(traffic["job"])
    job.update(traffic.get("job_by_config", {}).get(arch["name"], {}))
    mcfg = model_config(arch, traffic["sequence_length"], **job.get("model", {}))
    tcfg = TrainConfig(
        batch_size=job["sequences_per_chip"] * len(devices), save_final=False,
        seed=seed % (2 ** 31), **job.get("train", {}),
    )
    mesh_cfg = MeshConfig(**arch.get("mesh", {}))
    cfg = Config(model=mcfg, train=tcfg, mesh=mesh_cfg)
    mesh = build_mesh(mesh_cfg, devices) if (len(devices) > 1 or needs_mesh(mesh_cfg)) else None
    return cfg, mesh
