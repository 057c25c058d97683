"""Share of the held experts' slots that pad a token count up to its slot
count, from the program's moe_expert_slots_total counter."""


def read(ctx):
    series = ctx.snapshot.get("moe_expert_slots_total", {}).get("series", [])
    counts = {}
    for s in series:
        kind = s["labels"].get("kind")
        counts[kind] = counts.get(kind, 0.0) + s["value"]
    total = counts.get("real", 0.0) + counts.get("pad", 0.0)
    if not total:
        return None
    return 100.0 * counts.get("pad", 0.0) / total
