"""One counter family of the program's global MetricsRegistry over
another, both summed over their cells and counted since the process
began (warm-up included: a ratio of two counts made at the same place
does not depend on how many steps ran). Nothing where the denominator is
absent or 0."""


def total(families, name):
    for family in families:
        if family.name == name:
            return sum(s.value for s in family.samples if s.suffix == "")
    return None


def read(ctx, numerator, denominator):
    from keystone_tpu.observability.registry import get_global_registry

    families = get_global_registry().collect()
    den = total(families, denominator)
    if not den:
        return None
    return (total(families, numerator) or 0.0) / den
