"""Plain float32 references, one per architecture family."""
