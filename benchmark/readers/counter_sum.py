"""The sum of one counter family's samples whose labels match, in the
program's global MetricsRegistry, counted since the process began
(set-up included: these are a run's counts and seconds, not shares).
Nothing where the family is absent (a program that does not count it);
0.0 where it is there and no sample matches: a run in which nothing of
the kind happened."""


def read(ctx, family, labels):
    from keystone_tpu.observability.registry import get_global_registry

    for found in get_global_registry().collect():
        if found.name == family:
            return float(sum(
                s.value for s in found.samples
                if s.suffix == "" and all(
                    s.labels.get(k) == v for k, v in labels.items())
            ))
    return None
