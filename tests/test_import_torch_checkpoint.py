"""Reference-checkpoint import: torch .pt -> framework checkpoint -> decode.

Builds a synthetic checkpoint in the reference's exact state-dict layout
(torch.save({'model_state_dict': ...}), per-head K/Q/V Linears, no W_O,
ReLU MLP, untied biased lm_head — reference scripts/train_transformer.py:104
+ src/models/*), imports it, and checks the imported model's logits against
an independent numpy forward of the reference semantics (written from the
SURVEY §2.5 spec, not the reference code).
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")

import jax
import jax.numpy as jnp

from pretraining_llm_tpu.models import transformer
from scripts.import_torch_checkpoint import _strip_prefixes, import_state_dict

V, T, D, H, L = 89, 16, 24, 3, 2
DH = D // H


def _make_reference_state_dict(seed=0):
    g = torch.Generator().manual_seed(seed)
    sd = {}

    def t(*shape):
        return torch.randn(*shape, generator=g) * 0.2

    sd["token_embed.weight"] = t(V, D)
    sd["position_embed.weight"] = t(T, D)
    for i in range(L):
        sd[f"attn_blocks.{i}.ln1.weight"] = 1 + 0.1 * t(D)
        sd[f"attn_blocks.{i}.ln1.bias"] = 0.1 * t(D)
        for h in range(H):
            for name in ("query", "key", "value"):
                sd[f"attn_blocks.{i}.attn.heads.{h}.{name}.weight"] = t(DH, D)
            # per-head mask buffer the importer must drop (reference B10)
            sd[f"attn_blocks.{i}.attn.heads.{h}.tril"] = torch.tril(
                torch.ones(T, T)
            )
        sd[f"attn_blocks.{i}.ln2.weight"] = 1 + 0.1 * t(D)
        sd[f"attn_blocks.{i}.ln2.bias"] = 0.1 * t(D)
        sd[f"attn_blocks.{i}.mlp.hidden.weight"] = t(4 * D, D)
        sd[f"attn_blocks.{i}.mlp.hidden.bias"] = 0.1 * t(4 * D)
        sd[f"attn_blocks.{i}.mlp.proj.weight"] = t(D, 4 * D)
        sd[f"attn_blocks.{i}.mlp.proj.bias"] = 0.1 * t(D)
    sd["layer_norm.weight"] = 1 + 0.1 * t(D)
    sd["layer_norm.bias"] = 0.1 * t(D)
    sd["lm_head.weight"] = t(V, D)
    sd["lm_head.bias"] = 0.1 * t(V)
    sd["pos_idxs"] = torch.arange(T)
    return sd


def _reference_forward_numpy(sd, tokens):
    """Independent numpy forward of the SURVEY §2.5 semantics."""

    def ln(x, w, b):
        mu = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        return (x - mu) / np.sqrt(var + 1e-5) * w + b

    np_sd = {k: v.numpy().astype(np.float64) for k, v in sd.items() if v.dtype.is_floating_point}
    x = np_sd["token_embed.weight"][tokens] + np_sd["position_embed.weight"][: tokens.shape[1]]
    mask = np.tril(np.ones((tokens.shape[1], tokens.shape[1]), bool))
    for i in range(L):
        hld = ln(x, np_sd[f"attn_blocks.{i}.ln1.weight"], np_sd[f"attn_blocks.{i}.ln1.bias"])
        heads = []
        for h in range(H):
            q = hld @ np_sd[f"attn_blocks.{i}.attn.heads.{h}.query.weight"].T
            k = hld @ np_sd[f"attn_blocks.{i}.attn.heads.{h}.key.weight"].T
            v = hld @ np_sd[f"attn_blocks.{i}.attn.heads.{h}.value.weight"].T
            s = q @ k.transpose(0, 2, 1) / np.sqrt(DH)
            s = np.where(mask, s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            p = p / p.sum(-1, keepdims=True)
            heads.append(p @ v)
        x = x + np.concatenate(heads, -1)
        hld = ln(x, np_sd[f"attn_blocks.{i}.ln2.weight"], np_sd[f"attn_blocks.{i}.ln2.bias"])
        hid = np.maximum(
            hld @ np_sd[f"attn_blocks.{i}.mlp.hidden.weight"].T
            + np_sd[f"attn_blocks.{i}.mlp.hidden.bias"],
            0.0,
        )
        x = x + hid @ np_sd[f"attn_blocks.{i}.mlp.proj.weight"].T + np_sd[
            f"attn_blocks.{i}.mlp.proj.bias"
        ]
    x = ln(x, np_sd["layer_norm.weight"], np_sd["layer_norm.bias"])
    return x @ np_sd["lm_head.weight"].T + np_sd["lm_head.bias"]


def test_import_matches_reference_semantics(tmp_path):
    sd = _make_reference_state_dict()
    pt = tmp_path / "reference.pt"
    # Reference schema incl. DDP/compile prefixes the importer must strip.
    torch.save(
        {"model_state_dict": {f"module._orig_mod.{k}": v for k, v in sd.items()}},
        pt,
    )

    raw = torch.load(pt, map_location="cpu", weights_only=True)
    clean = _strip_prefixes({k: v.numpy() for k, v in raw["model_state_dict"].items()})
    clean = {k: v for k, v in clean.items() if not k.endswith((".tril", "pos_idxs"))}
    cfg, params = import_state_dict(clean)

    assert cfg.vocab_size == V and cfg.n_layers == L and cfg.n_heads == H
    assert not cfg.use_output_proj and not cfg.tie_embeddings and cfg.lm_head_bias

    tokens = np.arange(2 * T).reshape(2, T) % V
    want = _reference_forward_numpy(sd, tokens)

    import dataclasses

    fcfg = dataclasses.replace(cfg, compute_dtype="float32")
    params_j = jax.tree.map(jnp.asarray, params)
    got, _ = transformer.forward(params_j, jnp.asarray(tokens), fcfg)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_import_cli_roundtrip_generates(tmp_path):
    """Full CLI path: torch.save -> import script -> generate_text loads it."""
    import os
    import subprocess
    import sys

    sd = _make_reference_state_dict(seed=1)
    pt = tmp_path / "ref.pt"
    torch.save({"model_state_dict": sd}, pt)
    out_dir = tmp_path / "imported"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(repo_root, "scripts", "import_torch_checkpoint.py"),
         str(pt), "--out_dir", str(out_dir), "--tokenizer", "byte"],
        capture_output=True, text=True, env=env, timeout=300, cwd=repo_root,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "imported" in r.stdout

    from pretraining_llm_tpu.generation.generate import generate_text

    text = generate_text(str(out_dir), "ab", max_new_tokens=4, seed=0)
    assert text.startswith("ab") and len(text) > 2


def test_import_rejects_unmapped_weights():
    """Extra trained weights (a deviated architecture) fail loudly."""
    sd = {
        k: v.numpy()
        for k, v in _make_reference_state_dict().items()
        if v.dtype.is_floating_point and not k.endswith(".tril")
    }
    sd["attn_blocks.0.attn.proj.weight"] = np.zeros((D, D), np.float32)
    with pytest.raises(ValueError, match="does not map"):
        import_state_dict(sd)


def test_strip_prefixes_handles_compile_of_ddp():
    sd = {"_orig_mod.module.token_embed.weight": 1, "module.x": 2, "y": 3}
    assert set(_strip_prefixes(sd)) == {"token_embed.weight", "x", "y"}


def test_export_import_roundtrip_identity():
    """export_params is the exact inverse of import_state_dict."""
    from scripts.export_torch_checkpoint import export_params

    sd = {
        k: v.numpy()
        for k, v in _make_reference_state_dict(seed=2).items()
        if v.dtype.is_floating_point and not k.endswith(".tril")
    }
    cfg, params = import_state_dict(sd)
    back = export_params(cfg, params)
    for k in sd:
        np.testing.assert_array_equal(back[k], sd[k], err_msg=k)
    # Export also synthesizes the reference's registered buffers so its
    # strict load_state_dict finds every key.
    extra = set(back) - set(sd)
    assert extra == {"pos_idxs"} | {
        f"attn_blocks.{i}.attn.heads.{h}.tril" for i in range(L) for h in range(H)
    }


def test_export_rejects_non_reference_shapes():
    from scripts.export_torch_checkpoint import export_params

    from pretraining_llm_tpu.config import get_preset
    from pretraining_llm_tpu.models import transformer as tf

    cfg = get_preset("tiny").model  # standard GPT-2 shape: W_O + tied head
    params = tf.init_params(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="reference-shaped"):
        export_params(cfg, params)
