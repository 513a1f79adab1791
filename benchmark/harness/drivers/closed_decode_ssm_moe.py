"""``closed_decode_ssm`` for a state-slot model whose FFNs are routed experts:
`correct` holds every expert layer by itself on the reference's input, beside
the logits and the state slots.

``closed_decode_ssm.run``, unedited, with the module it asks for the comparison
(``ssm_check``) exchanged for ``ssm_moe_check`` for the length of the call, as
that driver exchanges ``closed_decode``'s: the same loop, tick log, staggered
first wave and window rule, the same prefill and teacher-forced steps, the same
``logits_rel_err``, ``state_rel_err`` and ``state_first_rel_err``, and
``expert_layer_rel_err`` (the router's and the experts' precision, which logits
that carry routing flips cannot tell: ``ssm_moe_check``'s text). The engine's own
tokens are held as ``closed_decode`` holds them.
"""

from __future__ import annotations

from harness import ssm_moe_check
from harness.context import Ctx, RunResult
from harness.drivers import closed_decode_ssm


def run(ctx: Ctx) -> RunResult:
    theirs = closed_decode_ssm.ssm_check
    closed_decode_ssm.ssm_check = ssm_moe_check
    try:
        return closed_decode_ssm.run(ctx)
    finally:
        closed_decode_ssm.ssm_check = theirs
