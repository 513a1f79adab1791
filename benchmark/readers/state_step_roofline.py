"""A whole decode step's share of its HBM roofline, for a dense model whose
recurrent layers keep a state a row (``harness/families/<family>.py``:
``decode_step_min_bytes(arch, resident_tokens, rows)``, the family's own bytes:
every weight once, every row's state read and written, every resident token's
K and V in the attention layers alone). ``readers/decode_hbm_roofline.py``
counts ``opcount``'s bytes, pages in every layer and no state;
``readers/part_roofline.py`` serves the expert models and waits for their
routing counters. The least time is bytes over peak bytes/s; the time is the
decode program's mean device time a run. None where the family counts no state
(``state_bytes_per_row``) or its count wants the experts touched, or the trace
has no decode program, as the parent's gives."""

from harness import families, peaks
from readers import module_time


def read(result, summary, ctx, match):
    fam, obs = families.of(ctx.arch), result.observed
    runs = module_time.runs(summary, match)
    if (not runs or not obs.get("resident_tokens") or not hasattr(fam, "state_bytes_per_row")
            or hasattr(fam, "moe_step_bytes")):
        return None
    need = fam.decode_step_min_bytes(ctx.arch, obs["resident_tokens"], obs["rows"])
    return 100.0 * need / peaks.peak(ctx.devices[0].device_kind, "hbm_bytes_per_s") / (sum(runs) / len(runs))
