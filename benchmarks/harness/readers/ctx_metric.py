"""A counter of the engine's `ctx.metrics`, summed over the window's
collects; `"per": "query"` divides by the queries answered."""


def read(spec, run):
    records = [r for r in run["window"].records if r.error is None]
    if not records:
        return None
    total = sum(float(r.metrics.get(spec["key"], 0) or 0) for r in records)
    return total / len(records) if spec.get("per") == "query" else total
