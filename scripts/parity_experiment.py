#!/usr/bin/env python
"""Eval-loss parity: this framework vs an independent PyTorch twin.

BASELINE.json's bar is "eval loss matching the GPU baseline +-0.01". This
environment has no GPU and no network, so the baseline is produced the way
the reference would have produced it: a from-scratch PyTorch training run
(torch CPU, fp32) of the SAME architecture, from the SAME initial weights,
on the SAME real-text byte stream in the SAME batch order, with the same
AdamW/clip/schedule math. The only remaining differences are framework
numerics (XLA:TPU vs torch CPU kernels, reduction orders) — exactly what the
parity bar is meant to measure.

Corpus: real English prose harvested from the machine itself (package READMEs,
documentation, license texts — ~3.5 MB), byte-level tokenized (vocab 256).
No synthetic data anywhere.

Usage:
  python scripts/parity_experiment.py            # full pipeline
  python scripts/parity_experiment.py --steps 1500 --eval-iters 50

Writes data/parity/{corpus.txt,train.bin,val.bin,init.npz,results.json} and
prints the result as a markdown table row.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pretraining_llm_tpu.utils.compile_cache import use_compile_cache

use_compile_cache()

PARITY_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "data", "parity")

# Small GPT-2-shape model (standard mode: fused QKV, output projection, tied
# embeddings, GELU, learned positions), fp32 both sides so numerics are
# comparable at the +-0.01 bar.
MODEL_KW = dict(
    vocab_size=256,
    context_length=256,
    d_model=256,
    n_heads=8,
    n_layers=4,
    activation="gelu",
    pos_embed="learned",
    tie_embeddings=True,
    qkv_bias=False,
    mlp_bias=True,
    param_dtype="float32",
    compute_dtype="float32",
)
BATCH = 16
LR = 3e-4
WARMUP_FRAC = 0.05
GRAD_CLIP = 1.0
WEIGHT_DECAY = 0.1
B1, B2, EPS = 0.9, 0.95, 1e-8
DATA_SEED = 1234
EVAL_SEED = 4321


# ---------------------------------------------------------------------------
# Corpus: real English prose available on an air-gapped machine
# ---------------------------------------------------------------------------


def build_corpus(path: str, max_bytes: int = 6_000_000) -> int:
    roots = [
        "/opt/venv/lib/python3.12/site-packages",
        "/usr/share/common-licenses",
        "/THIRD_PARTY_NOTICES",
    ]
    files = []
    for root in roots:
        for dirpath, _, names in os.walk(root):
            for name in names:
                if name.endswith((".rst", ".md")) or name in (
                    "LICENSE", "LICENSE.txt", "LICENSES.txt", "README.txt",
                    "GPL-2", "GPL-3", "LGPL-2", "LGPL-2.1", "LGPL-3", "Apache-2.0",
                    "BSD", "MPL-1.1", "MPL-2.0", "Artistic",
                ):
                    p = os.path.join(dirpath, name)
                    try:
                        if os.path.getsize(p) > 2000 and not os.path.islink(p):
                            files.append(p)
                    except OSError:
                        continue
    files.sort()  # deterministic order
    total = 0
    with open(path, "wb") as out:
        for p in files:
            if total >= max_bytes:
                break
            try:
                data = open(p, "rb").read()
            except OSError:
                continue
            # keep printable-ish text only; skip binary-looking files
            if b"\x00" in data:
                continue
            out.write(data)
            out.write(b"\n\n")
            total += len(data) + 2
    return total


def tokenize_corpus(corpus_path: str, train_path: str, val_path: str) -> None:
    raw = np.frombuffer(open(corpus_path, "rb").read(), dtype=np.uint8).astype(np.uint16)
    n_val = len(raw) // 20  # 5% validation split
    raw[: len(raw) - n_val].tofile(train_path)
    raw[len(raw) - n_val :].tofile(val_path)


# ---------------------------------------------------------------------------
# JAX side (the framework under test)
# ---------------------------------------------------------------------------


def run_jax(args, model_cfg, train_path, val_path, init_npz):
    import jax
    import jax.numpy as jnp

    from pretraining_llm_tpu.config import Config, TrainConfig
    from pretraining_llm_tpu.data import loader
    from pretraining_llm_tpu.models import transformer
    from pretraining_llm_tpu.training import train_step as ts

    cfg = Config(
        model=model_cfg,
        train=TrainConfig(
            batch_size=BATCH, lr=LR, train_steps=args.steps,
            lr_schedule="warmup_constant", warmup_frac=WARMUP_FRAC,
            grad_clip=GRAD_CLIP, weight_decay=WEIGHT_DECAY,
            adam_b1=B1, adam_b2=B2, adam_eps=EPS,
            checkpoint_interval=0, eval_interval=0,
        ),
        name="parity",
    )
    # True-f32 matmuls: on TPU, jax's default "fastest" precision runs f32
    # einsums as bf16 MXU passes — a real numeric difference vs the torch
    # CPU baseline that compounds over steps. The parity bar measures
    # framework math, not matmul rounding mode.
    jax.config.update("jax_default_matmul_precision", "highest")
    state = ts.init_train_state(cfg, jax.random.key(0))
    if os.path.exists(init_npz):
        # The committed init.npz is an ARTIFACT: results.json pins its sha
        # (init_sha), so a rerun must LOAD it — not regenerate and overwrite
        # it, which silently rebased the recorded identity every time the
        # experiment ran (and made the banked curves unreproducible when the
        # init routine drifted). Delete the file to start a fresh experiment.
        raw = dict(np.load(init_npz))
        saved_kw = (
            json.loads(bytes(raw.pop("__model_kw__")).decode())
            if "__model_kw__" in raw else None
        )
        if saved_kw != json.loads(json.dumps(MODEL_KW, sort_keys=True)):
            raise ValueError(
                f"{init_npz} was written for a different MODEL_KW — delete "
                "it to regenerate (the recorded curves will no longer be "
                "comparable)."
            )
        flat, treedef = jax.tree_util.tree_flatten_with_path(state["params"])
        leaves = []
        for path, leaf in flat:
            key = "__".join(str(getattr(e, "key", e)) for e in path)
            if key not in raw:
                raise ValueError(
                    f"{init_npz} is missing param {key!r} — delete it to "
                    "regenerate."
                )
            if raw[key].shape != leaf.shape:
                raise ValueError(
                    f"{init_npz} param {key!r} has shape {raw[key].shape}, "
                    f"model wants {leaf.shape} — delete it to regenerate."
                )
            leaves.append(jnp.asarray(raw[key], leaf.dtype))
        state["params"] = jax.tree_util.tree_unflatten(treedef, leaves)
        print(f"[jax] loaded shared init from {init_npz}", flush=True)
    else:
        # First run: persist the exact initial weights for the torch twin.
        flat = jax.tree_util.tree_flatten_with_path(state["params"])[0]
        np.savez(
            init_npz,
            __model_kw__=np.frombuffer(json.dumps(MODEL_KW, sort_keys=True).encode(), np.uint8),
            **{
                "__".join(str(getattr(e, "key", e)) for e in path): np.asarray(leaf, np.float32)
                for path, leaf in flat
            },
        )
    step = ts.build_train_step(cfg, mesh=None)
    it = loader.get_batch_iterator(
        train_path, BATCH, model_cfg.context_length, seed=DATA_SEED
    )

    eval_step = jax.jit(
        lambda p, x, y: transformer.loss_fn(p, x, y, model_cfg, include_aux=False)
    )

    def eval_loss(params):
        ev = loader.get_batch_iterator(
            val_path, BATCH, model_cfg.context_length, seed=EVAL_SEED
        )
        total = 0.0
        for _ in range(args.eval_iters):
            x, y = next(ev)
            total += float(eval_step(params, jnp.asarray(x), jnp.asarray(y)))
        return total / args.eval_iters

    curve = []
    for s in range(args.steps):
        x, y = next(it)
        state, metrics = step(state, (jnp.asarray(x), jnp.asarray(y)))
        if (s + 1) % args.log_every == 0 or s == 0:
            curve.append({"step": s + 1, "loss": float(metrics["loss"])})
            print(f"[jax] step {s+1} loss {curve[-1]['loss']:.4f}", flush=True)
    final_eval = eval_loss(state["params"])
    print(f"[jax] final eval loss {final_eval:.4f}")
    return {"curve": curve, "eval_loss": final_eval, "backend": jax.default_backend(),
            "steps": args.steps}


# ---------------------------------------------------------------------------
# Torch side (the independent baseline)
# ---------------------------------------------------------------------------


def run_torch(args, model_cfg, train_path, val_path, init_npz):
    import torch

    from pretraining_llm_tpu.data import loader

    torch.manual_seed(0)
    torch.set_num_threads(os.cpu_count() or 8)
    d, h, dh, f, L = (
        model_cfg.d_model, model_cfg.n_heads, model_cfg.head_dim,
        model_cfg.d_ff, model_cfg.n_layers,
    )
    eps_ln = model_cfg.norm_eps
    if not os.path.exists(init_npz):
        raise FileNotFoundError(
            f"{init_npz} not found: the jax side writes the shared initial "
            "weights — run without --only torch first (or with --only jax)."
        )
    raw = dict(np.load(init_npz))
    saved_kw = json.loads(bytes(raw.pop("__model_kw__")).decode()) if "__model_kw__" in raw else None
    if saved_kw is not None and saved_kw != json.loads(json.dumps(MODEL_KW, sort_keys=True)):
        raise ValueError(
            "init.npz was written for a different MODEL_KW — rerun the jax "
            "side so both twins start from the same weights."
        )
    P = {k: torch.nn.Parameter(torch.from_numpy(v.copy())) for k, v in raw.items()}

    def forward(tokens):
        x = P["tok_embed__embedding"][tokens] + P["pos_embed__embedding"][None, : tokens.shape[1]]
        t = tokens.shape[1]
        mask = torch.tril(torch.ones(t, t, dtype=torch.bool))
        for li in range(L):
            ln1 = torch.nn.functional.layer_norm(
                x, (d,), P["blocks__ln1__scale"][li], P["blocks__ln1__bias"][li], eps=eps_ln
            )
            qkv = torch.einsum("btd,dchn->bcthn", ln1, P["blocks__attn__wqkv"][li])
            q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
            att = torch.einsum("bqhd,bkhd->bhqk", q, k) / (dh**0.5)
            att = att.masked_fill(~mask[None, None], float("-inf"))
            out = torch.einsum("bhqk,bkhd->bqhd", torch.softmax(att, -1), v)
            x = x + torch.einsum("bthn,hnd->btd", out, P["blocks__attn__wo"][li]) + P["blocks__attn__bo"][li]
            ln2 = torch.nn.functional.layer_norm(
                x, (d,), P["blocks__ln2__scale"][li], P["blocks__ln2__bias"][li], eps=eps_ln
            )
            hidden = torch.nn.functional.gelu(
                ln2 @ P["blocks__mlp__w1"][li] + P["blocks__mlp__b1"][li], approximate="tanh"
            )
            x = x + hidden @ P["blocks__mlp__w2"][li] + P["blocks__mlp__b2"][li]
        x = torch.nn.functional.layer_norm(
            x, (d,), P["final_norm__scale"], P["final_norm__bias"], eps=eps_ln
        )
        return x @ P["tok_embed__embedding"].T  # tied head

    def ce(tokens, targets):
        logits = forward(tokens)
        return torch.nn.functional.cross_entropy(
            logits.reshape(-1, logits.shape[-1]), targets.reshape(-1)
        )

    # Decay mask mirrors optimizer.decay_mask (leaf-name based).
    decay_names = ("wqkv", "wo", "w1", "w2", "kernel", "embedding")
    decay = [p for k, p in P.items() if k.split("__")[-1] in decay_names]
    no_decay = [p for k, p in P.items() if k.split("__")[-1] not in decay_names]
    opt = torch.optim.AdamW(
        [
            {"params": decay, "weight_decay": WEIGHT_DECAY},
            {"params": no_decay, "weight_decay": 0.0},
        ],
        lr=LR, betas=(B1, B2), eps=EPS,
    )

    def lr_at(s):
        warm = max(WARMUP_FRAC * args.steps, 1.0)
        return min(LR * (s + 1.0) / warm, LR)

    it = loader.get_batch_iterator(
        train_path, BATCH, model_cfg.context_length, seed=DATA_SEED
    )
    curve = []
    for s in range(args.steps):
        x, y = next(it)
        for gp in opt.param_groups:
            gp["lr"] = lr_at(s)
        opt.zero_grad(set_to_none=True)
        loss = ce(torch.from_numpy(x).long(), torch.from_numpy(y).long())
        loss.backward()
        # Same clip formula as training.optimizer.clip_by_global_norm.
        with torch.no_grad():
            norm = torch.sqrt(sum((p.grad.float() ** 2).sum() for p in P.values()))
            scale = min(1.0, GRAD_CLIP / (float(norm) + 1e-9))
            if scale < 1.0:
                for p in P.values():
                    p.grad.mul_(scale)
        opt.step()
        if (s + 1) % args.log_every == 0 or s == 0:
            curve.append({"step": s + 1, "loss": loss.item()})
            print(f"[torch] step {s+1} loss {loss.item():.4f}", flush=True)

    ev = loader.get_batch_iterator(
        val_path, BATCH, model_cfg.context_length, seed=EVAL_SEED
    )
    with torch.no_grad():
        total = 0.0
        for _ in range(args.eval_iters):
            x, y = next(ev)
            total += ce(torch.from_numpy(x).long(), torch.from_numpy(y).long()).item()
    final_eval = total / args.eval_iters
    print(f"[torch] final eval loss {final_eval:.4f}")
    return {"curve": curve, "eval_loss": final_eval, "backend": "torch-cpu",
            "steps": args.steps}


# ---------------------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=1500)
    ap.add_argument("--eval-iters", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=100)
    ap.add_argument("--rebuild-corpus", action="store_true")
    ap.add_argument("--only", choices=["", "jax", "torch"], default="")
    args = ap.parse_args()

    from pretraining_llm_tpu.config import ModelConfig

    model_cfg = ModelConfig(**MODEL_KW)
    os.makedirs(PARITY_DIR, exist_ok=True)
    corpus = os.path.join(PARITY_DIR, "corpus.txt")
    train_bin = os.path.join(PARITY_DIR, "train.bin")
    val_bin = os.path.join(PARITY_DIR, "val.bin")
    init_npz = os.path.join(PARITY_DIR, "init.npz")
    results_path = os.path.join(PARITY_DIR, "results.json")

    # Rebuild only when missing (or forced): the harvest walks a LIVE
    # filesystem, so an implicit rebuild between --only jax and --only torch
    # could silently train the twins on different data.
    if args.rebuild_corpus or not os.path.exists(train_bin):
        n = build_corpus(corpus)
        tokenize_corpus(corpus, train_bin, val_bin)
        print(f"corpus: {n/1e6:.2f} MB real text -> {train_bin}")

    def _steps_of(rec):
        """(count, exact). Pre-"steps" records fall back to the last LOGGED
        step — a LOWER bound (the true count may exceed it by up to
        log_every-1), so callers must only flag mismatches they can prove."""
        if rec.get("steps") is not None:
            return rec["steps"], True
        curve = rec.get("curve") or []
        return (curve[-1]["step"], False) if curve else (None, False)

    def _proven_mismatch(a, a_exact, b, b_exact):
        if a is None or b is None:
            return False
        if a_exact and b_exact:
            return a != b
        # An exact count strictly below the other side's lower bound is the
        # only provable mismatch; two bounds prove nothing.
        if a_exact and not b_exact:
            return a < b
        if b_exact and not a_exact:
            return b < a
        return False

    results = {}
    if os.path.exists(results_path):
        results = json.load(open(results_path))

    # The delta only means something when both twins trained the same number
    # of steps — and a partial --only rerun at a different --steps must be
    # refused BEFORE it trains and overwrites the banked matching record
    # (this exact mistake produced a spurious "delta 1.1571 FAIL" and
    # destroyed a 1500-step record: a 300-step `--only jax` rerun compared
    # against — and clobbered — the recorded 1500-step twin).
    # Corpus-identity guard: the harvest walks a LIVE filesystem, so a
    # record trained in another container could (if the image ever
    # changes) sit on DIFFERENT data than the local train.bin — a partial
    # --only rerun would then compare curves across corpora and bank a
    # spurious delta. Records carry the corpus sha; a mismatch against the
    # recorded other side refuses before training. (The corpus bins are
    # also committed now, so a fresh container gets the exact bytes.)
    import hashlib

    def _file_sha(path: str) -> str:
        return hashlib.sha256(open(path, "rb").read()).hexdigest()

    def _corpus_sha() -> str:
        # The data streams the delta depends on: the train stream and the
        # val set eval_loss is measured on. The shared initial weights are
        # a SEPARATE identity (init_sha): the jax side writes init.npz on
        # a first run (and loads it thereafter), so folding it in here
        # would make the value depend on run order.
        h = hashlib.sha256(open(train_bin, "rb").read())
        h.update(open(val_bin, "rb").read())
        return h.hexdigest()

    corpus_sha = _corpus_sha()

    if args.only in ("jax", "torch"):
        other = results.get({"jax": "torch", "torch": "jax"}[args.only])
        other_sha = other.get("corpus_sha") if other else None
        if other_sha and other_sha != corpus_sha:
            print(json.dumps({
                "error": f"corpus mismatch: local train.bin+val.bin sha "
                         f"{corpus_sha[:16]} != recorded "
                         f"{'torch' if args.only == 'jax' else 'jax'} twin's "
                         f"{other_sha[:16]}; the twins would train on "
                         "different data — restore the committed "
                         "data/parity bins or retrain BOTH sides",
            }))
            return 2
        # init identity: --only torch READS the local init.npz — it must
        # be the exact weights the recorded jax twin started from.
        other_init = other.get("init_sha") if other else None
        if (
            args.only == "torch"
            and other_init
            and os.path.exists(init_npz)
            and _file_sha(init_npz) != other_init
        ):
            print(json.dumps({
                "error": f"init mismatch: local init.npz sha "
                         f"{_file_sha(init_npz)[:16]} != the recorded jax "
                         f"twin's {other_init[:16]}; the torch side would "
                         "train from different initial weights — restore "
                         "the committed data/parity/init.npz or retrain "
                         "BOTH sides",
            }))
            return 2
        so, so_exact = _steps_of(other) if other else (None, False)
        if _proven_mismatch(args.steps, True, so, so_exact):
            bound = "" if so_exact else "at least "
            print(json.dumps({
                "error": f"step-count mismatch: --only {args.only} with "
                         f"--steps {args.steps}, but the recorded "
                         f"{'torch' if args.only == 'jax' else 'jax'} twin "
                         f"ran {bound}{so} steps; rerun with a matching "
                         "--steps (or retrain both sides)",
            }))
            return 2

    if args.only in ("", "jax"):
        new_jax = run_jax(args, model_cfg, train_bin, val_bin, init_npz)
        new_jax["corpus_sha"] = corpus_sha
        # Post-run: the jax side LOADED an existing init.npz (or wrote it
        # on a first run) — stamp the file this run actually trained from,
        # and refuse if it doesn't match what the recorded torch twin
        # trained from (belt-and-braces: a hand-deleted/regenerated file
        # would otherwise silently compare curves across different inits).
        new_jax["init_sha"] = _file_sha(init_npz)
        rec_torch = results.get("torch")
        if (
            args.only == "jax"
            and rec_torch
            and rec_torch.get("init_sha")
            and rec_torch["init_sha"] != new_jax["init_sha"]
        ):
            print(json.dumps({
                "error": f"init drift: this jax run trained from init.npz "
                         f"sha {new_jax['init_sha'][:16]} but the recorded "
                         f"torch twin trained from "
                         f"{rec_torch['init_sha'][:16]} — the curves are "
                         "not comparable; restore the committed "
                         "data/parity/init.npz or retrain BOTH sides",
            }))
            return 2
        # A rerun on a DIFFERENT backend must not destroy the banked
        # record: the TPU pinned-precision capture is round evidence
        # (data/parity/results.json), and a casual CPU rerun would
        # silently overwrite it. Archive the displaced record under a
        # backend-suffixed key (the pattern jax_tpu_fastmatmul/jax_cpu
        # already follow).
        old_jax = results.get("jax")
        if old_jax and old_jax.get("backend") != new_jax.get("backend"):
            # Collision-safe: an existing archive (e.g. the banked
            # jax_cpu baseline) must never itself be overwritten.
            key = f"jax_{old_jax.get('backend', 'prev')}"
            n = 2
            while key in results:
                key = f"jax_{old_jax.get('backend', 'prev')}_{n}"
                n += 1
            results[key] = old_jax
        results["jax"] = new_jax
    if args.only in ("", "torch"):
        results["torch"] = run_torch(args, model_cfg, train_bin, val_bin, init_npz)
        results["torch"]["corpus_sha"] = corpus_sha
        # Post-run: in a full run, run_jax loaded (or first-run wrote)
        # init.npz and torch trained from those bytes — stamp the file
        # torch actually read.
        results["torch"]["init_sha"] = _file_sha(init_npz)
    with open(results_path, "w") as fh:
        json.dump(results, fh, indent=2)
        fh.write("\n")

    if "jax" in results and "torch" in results:
        sj, sj_exact = _steps_of(results["jax"])
        st, st_exact = _steps_of(results["torch"])
        if _proven_mismatch(sj, sj_exact, st, st_exact):
            # Belt-and-braces: records can still disagree (hand-edited file).
            print(json.dumps({
                "error": f"step-count mismatch: jax ran {sj} steps, torch ran "
                         f"{st}; rerun the shorter side with --steps "
                         f"{max(sj, st)} (or both with matching --steps)",
            }))
            return 2
        ja, to = results["jax"]["eval_loss"], results["torch"]["eval_loss"]
        delta = abs(ja - to)
        passed = delta <= 0.01
        print("\n=== PARITY ===")
        print(f"jax  ({results['jax']['backend']}): eval loss {ja:.4f}")
        print(f"torch (cpu fp32 baseline):          eval loss {to:.4f}")
        print(f"delta {delta:.4f}  ({'PASS' if passed else 'FAIL'} at +-0.01)")
        # Structured last line + nonzero exit on FAIL (ADVICE r3 medium):
        # a caller that keeps only rc and the last line would otherwise
        # record a FAIL that exits 0 as a pass.
        print(json.dumps({
            "delta": round(delta, 6),
            "pass": passed,
            "jax_eval_loss": ja,
            "torch_eval_loss": to,
            "jax_backend": results["jax"]["backend"],
            "steps": sj,
        }))
        if not passed:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
