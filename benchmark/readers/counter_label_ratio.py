"""The cells of one counter family whose ``label`` is ``value`` over
another family's total, both counted since the process began (as
``counter_ratio``). Nothing where the denominator is absent or 0."""

from benchmark.readers.counter_ratio import total


def read(ctx, numerator, label, value, denominator):
    from keystone_tpu.observability.registry import get_global_registry

    families = get_global_registry().collect()
    den = total(families, denominator)
    if not den:
        return None
    got = 0.0
    for family in families:
        if family.name == numerator:
            got = sum(s.value for s in family.samples
                      if s.suffix == "" and s.labels.get(label) == value)
    return got / den
