"""Counters a speculative engine writes on its ``serving.commit`` spans, over
the rounds committed inside the traced window (``generation/serving.py``):
``spec_proposed`` (drafts verified for rows that were still live),
``spec_accepted`` (of those, accepted) and ``spec_emitted`` (tokens the round
committed).

``stat="accept_rate"``: drafts accepted over drafts proposed, percent.
``stat="tokens_per_round"``: tokens committed a live row a round (one draft a
round: tokens over drafts proposed), between 1 and 2.
None when the trace has no such span or the spans carry no such counter, as a
plain decode window's and the parent's do."""

from readers import commit_counter


def read(result, summary, ctx, stat):
    ms = commit_counter.metas(ctx, "spec_proposed")
    proposed = sum(float(m["spec_proposed"]) for m in ms)
    if not proposed:
        return None
    if stat == "accept_rate":
        return 100.0 * sum(float(m["spec_accepted"]) for m in ms) / proposed
    if stat == "tokens_per_round":
        return sum(float(m["spec_emitted"]) for m in ms) / proposed
    raise ValueError(f"spec_counter has no stat {stat!r}")
