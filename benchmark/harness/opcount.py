"""Operations and bytes the algorithm needs, from shapes alone.

A copy of the arithmetic in ``ModelConfig.num_params``/``flops_per_token``
(the program may change its own), written against the benchmark's
configuration files: keys are the source's own (``n_embd`` / ``hidden_size``),
read by the family's module (``harness/families/<family>.py``).
Every function takes the ``arch`` dict that ``registry.load_config`` returns.
"""

from __future__ import annotations

from typing import Any, Dict

from harness import families


def dims(arch: Dict[str, Any]) -> Dict[str, int]:
    """The family's key names (GPT-2's, Mistral's, ...) normalised to one set."""
    return families.of(arch).dims(arch)


def layer_params(arch: Dict[str, Any]) -> int:
    """Parameters of one block, as the source architecture defines it."""
    return families.of(arch).layer_params(dims(arch))


def num_params(arch: Dict[str, Any]) -> int:
    """All parameters held: padded vocabulary rows count (they are stored and read)."""
    m = dims(arch)
    return m["layers"] * layer_params(arch) + families.of(arch).other_params(m)[0]


def train_flops_per_token(arch: Dict[str, Any], seq_len: int) -> int:
    """Forward + backward FLOPs a token of a ``seq_len`` sequence requires.

    6 x parameters (every weight is one multiply-add forward, two backward;
    a table that is only looked up, such as learned positions, is left out) plus the causal
    attention term: QK^T and PV are 2*T*d_attn FLOPs each per layer forward,
    x3 with the backward, halved because a causal model needs only the lower
    triangle. Recomputation under remat is not counted.
    """
    m = dims(arch)
    n = num_params(arch) - families.of(arch).other_params(m)[1]
    d_attn = m["heads"] * m["head_dim"]
    return 6 * n + 12 * m["layers"] * d_attn * seq_len // 2


def flash_train_flops_per_seq(arch: Dict[str, Any], seq_len: int) -> int:
    """Causal-halved FLOPs of the attention kernels alone, forward + backward,
    for one sequence over all layers: forward 2 matmuls, backward 5 (recomputed
    scores, dP, dV, dQ, dK) of 2*T*T*d_attn each."""
    m = dims(arch)
    d_attn = m["heads"] * m["head_dim"]
    return m["layers"] * 7 * 2 * seq_len * seq_len * d_attn // 2


def kv_bytes_per_token(arch: Dict[str, Any], bytes_per_el: int = 2) -> int:
    m = dims(arch)
    return 2 * m["kv_heads"] * m["head_dim"] * bytes_per_el * m["layers"]


def weight_bytes(arch: Dict[str, Any], bytes_per_el: int = 2) -> int:
    return num_params(arch) * bytes_per_el


def decode_step_min_bytes(arch: Dict[str, Any], resident_tokens: float,
                          rows: int, bytes_per_el: int = 2) -> float:
    """Bytes one decode step cannot avoid reading: every weight once (a table
    that is only looked up costs ``rows`` rows, not a read of the table)
    plus the K and V of every resident token once."""
    m = dims(arch)
    w = num_params(arch) - families.of(arch).other_params(m)[2] + rows * m["d"]
    return w * bytes_per_el + resident_tokens * kv_bytes_per_token(arch, bytes_per_el)
