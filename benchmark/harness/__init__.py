"""load cell -> build config -> Trainer.from_config -> fit -> read -> print."""


def say(msg: str) -> None:
    """A line of the run's record (stdout; the result object is the last)."""
    print(msg, flush=True)
