"""One number of the trainer's ``run_summary.json`` (dotted key)."""


def read(ctx, *, key):
    node = ctx["summary"]
    for part in key.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    return float(node) if isinstance(node, (int, float)) else None
