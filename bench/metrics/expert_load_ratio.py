"""The most loaded held expert's train-step assignments over the held
experts' mean, in the traced instance (the payloads' ``expert_tokens``
counter); 1 is an even load."""


def read(ctx):
    counts = ctx.counters.get("payload", {}).get("expert_tokens") or []
    mean = sum(counts) / len(counts) if counts else 0.0
    if not mean:
        return None
    return max(counts) / mean
