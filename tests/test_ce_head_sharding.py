"""The chunked CE head under a batch-sharded mesh (models/transformer.py).

Each device chunks its own tokens, the head weight is gathered once a pass
and dW summed once after the backward scan: no collective over the batch
axes inside either chunk scan. Structure is read from the compiled program
on the CPU's virtual devices, values are held to the single-device head.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding

from pretraining_llm_tpu.config import ModelConfig
from pretraining_llm_tpu.models import transformer as tr
from pretraining_llm_tpu.parallel import sharding as sh

AXES = ("data", "fsdp", "tensor", "seq", "expert", "pipe")
V, D, T, B = 512, 64, 64, 16  # V unlike every block width: the head's arrays are told by shape
CHUNKS_A_DEVICE = 4  # 4 x 64 tokens a device on four batch shards, 64 tokens a chunk

COLLECTIVE = re.compile(
    r" (all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)(?:-start)?\("
)


def _mesh(data=1, fsdp=1, tensor=1):
    n = data * fsdp * tensor
    devs = np.asarray(jax.devices()[:n]).reshape(data, fsdp, tensor, 1, 1, 1)
    return Mesh(devs, AXES)


def _cfg(tied, **kw):
    return ModelConfig(
        vocab_size=V, d_model=D, n_layers=1, n_heads=2, context_length=T,
        tie_embeddings=tied, lm_head_bias=not tied, **kw,
    )


@pytest.fixture
def small_chunks(monkeypatch):
    """The chunk rule at a toy size: 64 tokens of f32 logits a chunk."""
    monkeypatch.setattr(tr, "_CE_CHUNK_BYTES", 64 * V * 4)
    monkeypatch.setattr(tr, "_CE_MIN_CHUNK_TOKENS", 64)


def _batch():
    tok = jax.random.randint(jax.random.key(1), (B, T), 0, V)
    tgt = jax.random.randint(jax.random.key(2), (B, T), 0, V)
    return tok, tgt


def _sharded_value_and_grad(cfg, mesh, params):
    """(jitted value_and_grad of loss_fn under ``mesh``, sharded params, batch sharding)."""
    shardings = sh.named_sharding_tree(
        mesh, sh.param_pspec_tree(params, tensor_size=mesh.shape["tensor"])
    )
    batch = NamedSharding(mesh, sh.batch_pspec())

    def f(p, x, y):
        with sh.activation_mesh(mesh):
            return jax.value_and_grad(lambda q: tr.loss_fn(q, x, y, cfg))(p)

    fn = jax.jit(f, in_shardings=(shardings, batch, batch), out_shardings=(None, shardings))
    return fn, jax.device_put(params, shardings), batch


def _computations(hlo):
    """{name: text} of an HLO module's computations."""
    out, name, lines = {}, None, []
    for line in hlo.splitlines():
        if name is None:
            m = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\) -> .*\{$", line)
            if m:
                name, lines = m.group(1), []
        elif line == "}":
            out[name], name = "\n".join(lines), None
        else:
            lines.append(line)
    return out


def _reachable(comps, roots):
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        todo += re.findall(r"(?:body|condition|to_apply|calls)=(%[\w.\-]+)", comps[c])
        for group in re.findall(r"branch_computations=\{([^}]*)\}", comps[c]):
            todo += re.findall(r"%[\w.\-]+", group)
    return seen


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
@pytest.mark.parametrize("data,fsdp", [(1, 4), (2, 2)], ids=["fsdp4", "data2-fsdp2"])
def test_no_batch_collective_inside_the_head_scans(small_chunks, tied, data, fsdp):
    mesh = _mesh(data=data, fsdp=fsdp)
    cfg = _cfg(tied)
    params = tr.init_params(cfg, jax.random.key(0))
    fn, ps, _ = _sharded_value_and_grad(cfg, mesh, params)
    hlo = fn.lower(ps, *_batch()).compile().as_text()
    comps = _computations(hlo)
    ops = [l for text in comps.values() for l in text.splitlines()]

    # The two chunk scans of the head, each walking one device's own chunks.
    whiles = [l for l in ops if " while(" in l and "loss.ce" in l]
    assert len(whiles) == 2, whiles
    assert ["shard_map/while" in l for l in whiles] == [True, True]
    for l in whiles:
        assert f'"known_trip_count":{{"n":"{CHUNKS_A_DEVICE}"}}' in l, l
    # Nothing crosses devices inside them (the mesh has no tensor axis here,
    # so any collective in a body would be over the batch axes).
    roots = re.findall(r"(?:body|condition)=(%[\w.\-]+)", "\n".join(whiles))
    inside = [
        l for c in _reachable(comps, roots) for l in comps[c].splitlines() if COLLECTIVE.search(l)
    ]
    assert inside == []

    head = [l for l in ops if COLLECTIVE.search(l) and "loss.ce" in l]
    # The head weight, whole, once a pass (the compiler may share the
    # forward's copy with the backward; XLA:CPU gathers before the cast to
    # bf16, the TPU compiler after it).
    whole = rf"= (?:bf16|f32)\[(?:{D},{V}|{V},{D})\]\S* all-gather\("
    gathers = [l for l in head if re.search(whole, l)]
    assert 1 <= len(gathers) <= 2, gathers
    assert [l for l in head if " all-gather(" in l and l not in gathers] == []
    # dW: the devices' whole (D, V) f32 partial sums meet in one all-reduce
    # (XLA:CPU folds it into a tuple with the other gradients' sums), and no
    # other collective of the program moves the head's gradient.
    summed = [l for l in ops if " all-reduce(" in l and f"f32[{D},{V}]" in l.split(" all-reduce(")[0]]
    assert len(summed) == 1, summed
    assert [l for l in ops if " reduce-scatter(" in l] == []


@pytest.mark.parametrize(
    "tied,mesh_kw,z",
    [
        (True, dict(fsdp=4), 0.0),
        (False, dict(fsdp=4), 1e-3),
        (True, dict(data=2, fsdp=2), 1e-3),
        (False, dict(data=2, fsdp=2), 0.0),
        (True, dict(fsdp=2, tensor=2), 1e-3),
        (False, dict(data=2, fsdp=2, tensor=2), 0.0),
    ],
    ids=["tied-fsdp4", "untied-fsdp4-z", "tied-data2-fsdp2-z", "untied-data2-fsdp2",
         "tied-fsdp2-tensor2-z", "untied-data2-fsdp2-tensor2"],
)
def test_sharded_head_matches_single_device(small_chunks, tied, mesh_kw, z):
    mesh = _mesh(**mesh_kw)
    cfg = _cfg(tied, compute_dtype="float32", z_loss_coef=z)
    params = tr.init_params(cfg, jax.random.key(0))
    tok, tgt = _batch()
    loss0, grads0 = jax.jit(jax.value_and_grad(lambda p: tr.loss_fn(p, tok, tgt, cfg)))(params)
    fn, ps, batch = _sharded_value_and_grad(cfg, mesh, params)
    loss1, grads1 = fn(ps, jax.device_put(tok, batch), jax.device_put(tgt, batch))
    np.testing.assert_allclose(float(loss0), float(loss1), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=1e-3, atol=1e-6
        ),
        grads0, grads1,
    )


def _head_scans(cfg, batch, mesh=None):
    """(text of the traced gradient of loss_fn, its scan lengths): no array is made."""
    params = jax.eval_shape(lambda: tr.init_params(cfg, jax.random.key(0)))
    tok = jax.ShapeDtypeStruct((batch, cfg.context_length), jnp.int32)

    def f(p, x, y):
        with sh.activation_mesh(mesh):
            return jax.grad(lambda q: tr.loss_fn(q, x, y, cfg))(p)

    text = str(jax.make_jaxpr(f)(params, tok, tok))
    return text, sorted(int(n) for n in re.findall(r"\blength=(\d+)", text))


GPT2_HEAD = dict(vocab_size=50304, d_model=64, n_layers=1, n_heads=2, context_length=1024)


def test_no_mesh_trace_is_the_parents():
    # 12 x 1024 tokens of 50304 f32 logits: 6 chunks of 2048, as before this
    # head knew of meshes, and nothing of a mesh in the trace.
    assert tr._ce_n_chunks(12 * 1024, 50304) == 6
    text, lengths = _head_scans(ModelConfig(**GPT2_HEAD), 12)
    assert lengths == [1, 1, 6, 6]  # the layer scan and the head's, forward and backward
    assert "shard_map" not in text and "sharding_constraint" not in text


@pytest.mark.parametrize("mesh_kw", [dict(), dict(tensor=2)], ids=["all-ones", "tensor2"])
def test_batch_axes_of_extent_one_keep_the_global_head(mesh_kw):
    text, lengths = _head_scans(ModelConfig(**GPT2_HEAD), 12, _mesh(**mesh_kw))
    assert lengths == [1, 1, 6, 6] and "shard_map" not in text


def test_chunks_follow_the_tokens_a_device_holds():
    # gpt2-xl's cell: 4 x 12 x 1024 tokens over fsdp=4 are 6 chunks a device
    # (24 when counted from the global batch), inside a shard_map.
    text, lengths = _head_scans(ModelConfig(**GPT2_HEAD), 48, _mesh(fsdp=4))
    assert lengths == [1, 1, 6, 6] and text.count("shard_map") == 2


def test_a_batch_the_devices_do_not_divide_stays_with_the_partitioner():
    text, lengths = _head_scans(ModelConfig(**GPT2_HEAD), 6, _mesh(fsdp=4))
    assert lengths == [1, 1, 3, 3] and "shard_map" not in text
