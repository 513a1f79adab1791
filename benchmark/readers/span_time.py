"""``span_stat`` under a second name, for the metrics a later PR adds (see
``readers/scope_share.py`` for why)."""

from readers.span_stat import read  # noqa: F401
