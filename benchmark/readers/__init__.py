"""One module per reader kind; each has ``read(ctx, **args) -> float | None``.
A reader that finds nothing to read returns None."""
