"""``closed_decode`` for a model whose recurrent layers keep a state a row:
`correct` holds the state slots themselves to the reference, beside the logits.

The loop, the tick log, the staggered first wave and the window rule are
``closed_decode``'s own, unedited: this driver runs ``closed_decode.run`` with
the comparison of the decode step's logits (``serving_check.compare``)
exchanged for ``ssm_check.compare`` for the length of the call: the same
prefill and the same teacher-forced steps through slots and pool, the same
``logits_rel_err``, and ``state_rel_err`` from the slots the steps left behind
(the precision of the state, which the logits cannot tell: ``ssm_check``'s
text). The engine's own tokens are held as ``closed_decode`` holds them.
"""

from __future__ import annotations

import types
from typing import Any, Dict

from harness import serving_check, ssm_check
from harness.context import Ctx, RunResult
from harness.drivers import closed_decode


def run(ctx: Ctx) -> RunResult:
    found: Dict[str, Any] = {}

    def compare(ctx: Ctx, eng: Any, params: Any, cfg: Any):
        found.update(ssm_check.compare(ctx, eng, params, cfg))
        return found["logits_rel_err"]

    theirs = closed_decode.serving_check
    closed_decode.serving_check = types.SimpleNamespace(
        compare=compare, compare_tokens=serving_check.compare_tokens)
    try:
        result = closed_decode.run(ctx)
    finally:
        closed_decode.serving_check = theirs
    result.compared = {**found, **result.compared}
    return result
