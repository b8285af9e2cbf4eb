"""The readers this cell brings, on a hand-made trace summary: events
nested in a loop, inclusive against self time, spans by prefix."""

import types

from benchmark import trace as trace_lib
from benchmark.readers import spans_self_ms_per, trace_within_ms_per

MS = 1_000_000
CG = (r"^%while\S* = \(s32\[\]\S*, f32\[(\d+),(\d+)\]\S*, f32\[\1,\2\]\S*, "
      r"f32\[\1,\2\]")
LOOP = ("%while.20 = (s32[]{:T(128)}, f32[20,64]{1,0}, f32[20,64]{1,0}, "
        "f32[20,64]{1,0:S(1)}, f32[512,64]{1,0}) while(%tuple.1)")
OTHER_LOOP = ("%while.21 = (s32[]{:T(128)}, f32[64,64]{1,0}, s32[32,2]{1,0}) "
              "while(%tuple.2)")
PRODUCT = ("%fusion.3 = f32[512,20]{1,0} fusion(f32[512,64]{1,0} "
           "%get-tuple-element.7, f32[20,64]{1,0} %p), kind=kOutput")
SMALL = "%fusion.9 = f32[20,64]{1,0} fusion(f32[20,64]{1,0} %r), kind=kLoop"
GRAM = "%fusion.1 = f32[64,64]{1,0} fusion(f32[512,64]{1,0} %X.1), kind=kOutput"


def summary():
    ops = [
        (0 * MS, 100 * MS, "%step.marker = nothing"),  # keeps lo at 0
        (1 * MS, 5 * MS, GRAM),
        (6 * MS, 9 * MS, OTHER_LOOP),
        (10 * MS, 50 * MS, LOOP),
        (11 * MS, 21 * MS, PRODUCT), (21 * MS, 23 * MS, SMALL),
        (23 * MS, 35 * MS, PRODUCT), (40 * MS, 44 * MS, SMALL),
        (60 * MS, 64 * MS, PRODUCT.replace("%get-tuple-element.7", "%X.1")),
    ]
    host = [(0, 100 * MS, "bench:step"),
            (1 * MS, 2 * MS, "ks:solver.wls.prep"),
            (2 * MS, 4 * MS, "ks:solver.wls.dispatch"),
            (4 * MS, 54 * MS, "ks:solver.wls.converged"),
            (60 * MS, 70 * MS, "ks:solver.prep")]
    return trace_lib.TraceSummary({0: ops[1:]}, {0: []}, host, 1)


def ctx():
    c = types.SimpleNamespace()
    c.trace_summary = summary()
    c.config = {"num_features": 64, "name": "x", "generator": {"a": 1}}
    c.window = {"steps": 2, "work": 2 * 512}
    c.devices = [object()]
    return c


def test_inclusive_time_of_the_cg_loop_alone():
    # the CG loop's 40 ms over two steps; the solve's loop does not match
    got = trace_within_ms_per.read(ctx(), CG, inclusive=True)
    assert abs(got - 20.0) < 1e-9
    # its self time is what the nested events leave: 40 - 10 - 2 - 12 - 4
    assert abs(trace_within_ms_per.read(ctx(), CG) - 6.0) < 1e-9


def test_data_sized_products_inside_the_loop_only():
    pattern = r"fusion\(.*f32\[{rows},{num_features}\]"
    seconds, count = trace_within_ms_per.seconds_and_count(
        ctx(), pattern, CG, rows=512)
    assert count == 2 and abs(seconds - 0.022) < 1e-12
    # without the loop: the Gram and the product after the loop too
    _, everywhere = trace_within_ms_per.seconds_and_count(
        ctx(), pattern, rows=512)
    assert everywhere == 4
    # nothing matches: nothing is reported
    assert trace_within_ms_per.read(ctx(), r"no_such_op") is None


def test_spans_by_prefix():
    got = spans_self_ms_per.read(ctx(), "solver.wls.")
    assert abs(got - (1 + 2 + 50) / 2) < 1e-9
    assert spans_self_ms_per.read(ctx(), "solver.nothing.") is None
    c = ctx()
    c.trace_summary = None  # a CPU run
    assert spans_self_ms_per.read(c, "solver.wls.") is None
    assert trace_within_ms_per.read(c, CG) is None
