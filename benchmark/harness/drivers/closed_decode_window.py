"""``closed_decode`` for a model that keeps two cache lifetimes (window and
full attention layers in one stack): `correct` holds prefill and decode
through BOTH page pools to the reference, and every shape the loop will use is
compiled before it starts.

The loop, the tick log, the staggered first wave and the window rule are
``closed_decode``'s own, unedited: this driver runs ``closed_decode.run`` with

- the comparison of the decode step's logits (``serving_check.compare``, which
  builds one table from one allocator) exchanged for ``trinity_check.compare``
  (a row's two block lists, the window list kept page by page as the engine
  keeps it), and the tokens' for ``mtp_check.compare_tokens`` (the same
  regret, the head on the compared rows only), for the length of the call;
- the engine handed over warm: before the first wave one throwaway request of
  the steady state's shape (``prompt_tokens`` in, 2 out) runs to its end, so
  that the one-row admission program, which the loop first needs when its
  first request finishes, ``output_tokens / rows`` steps in and possibly past
  ``warm_ticks``, is compiled as set-up and not inside the window.

The engine's counters of the second lifetime (pages given back behind the
window, the most blocks either pool held) go into ``observed`` for the
readers and into the run's log.
"""

from __future__ import annotations

import types
from typing import Any, Dict

from harness import program, trinity_check
from harness.context import Ctx, RunResult
from harness.drivers import closed_decode

COUNTERS = ("window_pages_released", "window_blocks_peak", "kv_blocks_peak", "window_attn_pages_live",
            "window_attn_pages_tabled", "attn_pages_live", "attn_pages_tabled")


def run(ctx: Ctx) -> RunResult:
    found: Dict[str, Any] = {}
    seen: Dict[str, Any] = {}

    def warm_engine(params: Any, cfg: Any, traffic: Dict[str, Any]):
        eng = program.serving_engine(params, cfg, traffic)
        rid = eng.submit([0] * traffic["prompt_tokens"], 2)
        eng.run()
        eng.finished.pop(rid, None)
        eng.req_timing.pop(rid, None)
        return eng

    def compare(ctx: Ctx, eng: Any, params: Any, cfg: Any):
        info = eng.pool_info()
        seen.update({k: int(eng.stats[k]) for k in COUNTERS if k in eng.stats})
        seen["window_blocks_total"] = int(info.get("window_n_blocks", 1)) - 1
        ctx.log("two lifetimes (whole run): " + " ".join(f"{k}={v}" for k, v in seen.items())
                + f"; full pool {info.get('full_pool_bytes', 0) / 1e9:.3f} GB over {info.get('full_layers')} "
                f"layer(s), window pool {info.get('window_pool_bytes', 0) / 1e9:.3f} GB over "
                f"{info.get('window_layers')}; decode_attention {info['decode_attention']}, "
                f"decode_experts {info.get('decode_experts')}")
        found.update(trinity_check.compare(ctx, eng, params, cfg))
        return next(iter(found.values()))

    theirs = closed_decode.serving_check, closed_decode.program
    closed_decode.serving_check = types.SimpleNamespace(
        compare=compare, compare_tokens=trinity_check.compare_tokens)
    closed_decode.program = types.SimpleNamespace(
        model_config=program.model_config, serving_engine=warm_engine)
    try:
        result = closed_decode.run(ctx)
    finally:
        closed_decode.serving_check, closed_decode.program = theirs
    del result.compared["logits_rel_err"]  # the named comparison(s) stand in its place
    result.compared = {**found, **result.compared}
    result.observed.update(seen)
    return result
