"""``closed_decode`` for an engine that drafts for itself: every tick of the
loop is a speculative round (``spec_k`` in the traffic file's ``engine``
block, the model's own multi-token-prediction module as the draft), and
`correct` holds the round's two forwards to the reference.

The loop, the tick log, the staggered first wave and the window rule are
``closed_decode``'s own, unedited: this driver runs ``closed_decode.run`` with
the comparison of the decode step's logits (``serving_check.compare``, which
drives ``paged_decode_logits``) exchanged for the round's
(``mtp_check.compare``: the two-query verify program and the module, through
the pool at the cell's batch width), for the length of the call. The engine's
own tokens are held to the same number as in every serving cell
(``mtp_check.compare_tokens``: ``serving_check``'s regret with the head run on
the compared rows only; a greedy speculative engine emits the target's argmax
or a near-tie whatever was accepted). The rate counts committed tokens, one or two a row a round.

At seeded weights the module agrees with its stack at chance, so the rate is
the price of a round; the run's log and ``observed`` carry rounds, drafts
proposed and drafts accepted.
"""

from __future__ import annotations

import types
from typing import Any, Dict

from harness import mtp_check
from harness.context import Ctx, RunResult
from harness.drivers import closed_decode


def run(ctx: Ctx) -> RunResult:
    found: Dict[str, Any] = {}
    spec: Dict[str, int] = {}

    def compare(ctx: Ctx, eng: Any, params: Any, cfg: Any):
        if not getattr(eng, "self_draft", False):
            raise RuntimeError("the engine does not draft with the model's own module: "
                               "the traffic file's engine block needs spec_k and the model an MTP module")
        spec.update({k: int(eng.stats.get(k, 0)) for k in ("spec_rounds", "spec_proposed", "spec_accepted")})
        ctx.log("speculative rounds {spec_rounds}, drafts proposed {spec_proposed}, accepted {spec_accepted} "
                "(whole run); the engine says draft: {draft}, decode_attention: {decode_attention}".format(
                    **spec, **eng.pool_info()))
        found.update(mtp_check.compare(ctx, eng, params, cfg))
        return found["verify_logits_rel_err"]

    theirs = closed_decode.serving_check
    closed_decode.serving_check = types.SimpleNamespace(
        compare=compare, compare_tokens=mtp_check.compare_tokens)
    try:
        result = closed_decode.run(ctx)
    finally:
        closed_decode.serving_check = theirs
    del result.compared["logits_rel_err"]  # the round's two comparisons stand in its place
    result.compared = {**found, **result.compared}
    result.observed.update(spec)
    return result
