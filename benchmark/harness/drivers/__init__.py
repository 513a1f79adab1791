"""One module per traffic ``kind``; each exposes ``run(ctx) -> RunResult``."""
