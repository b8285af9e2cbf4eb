"""Per-rule fixture snippets: each of the seven rules proven to FIRE
on its defect pattern and to STAY QUIET on the compliant twin. The
snippets are miniature versions of the real incidents the rules
encode (tracer ring swap, build-under-pool-lock, chaos-row asserts,
zero-stamped MFU, per-row delivery slicing, catalog drift, dark
metric families)."""

import textwrap

import pytest

from keystone_tpu.analysis.core import FileContext, Project, run_analysis
from keystone_tpu.analysis.rules import (
    AbsentNotZeroRule,
    BlockingUnderLockRule,
    FaultPointDriftRule,
    GuardedByRule,
    HotPathHostSyncRule,
    MetricFamilyDriftRule,
    StrippableAssertRule,
)


def findings_for(rule, source, rel="pkg/mod.py"):
    ctx = FileContext(rel, rel, textwrap.dedent(source))
    return list(rule.check_file(ctx))


# -- guarded-by -------------------------------------------------------------


GUARDED_CLASS = """
import threading


class Pool:
    def __init__(self):
        self._lock = threading.Lock()
        self._ring = []  # guarded-by: _lock
        self._free = {{}}  # guarded-by: _lock

    def mutate(self):
        {body}
"""


@pytest.mark.parametrize(
    "body",
    [
        "self._ring = []",                      # rebind
        "self._ring += [1]",                    # augmented
        "self._free['k'] = 1",                  # item assign
        "self._ring.append(1)",                 # container mutation
        "self._free.setdefault('k', []).append(1)",
        "del self._free['k']",
    ],
)
def test_guarded_by_fires_on_unlocked_writes(body):
    fs = findings_for(
        GuardedByRule(), GUARDED_CLASS.format(body=body)
    )
    assert len(fs) == 1, fs
    assert fs[0].rule == "guarded-by"
    assert "_lock" in fs[0].message


@pytest.mark.parametrize(
    "body",
    [
        "with self._lock:\n            self._ring = []",
        "with self._lock:\n            self._ring.append(1)",
        "x = self._ring",            # reads are not writes
        "n = len(self._free)",
        "x = self._free.get('k')",   # non-mutating method
    ],
)
def test_guarded_by_quiet_on_locked_or_read(body):
    assert findings_for(
        GuardedByRule(), GUARDED_CLASS.format(body=body)
    ) == []


def test_guarded_by_exempts_init_and_locked_suffix():
    src = """
    import threading


    class Pool:
        def __init__(self):
            self._lock = threading.Lock()
            self._ring = []  # guarded-by: _lock
            self._ring = [1]  # re-init is still construction

        def _drop_locked(self):
            self._ring = []
    """
    assert findings_for(GuardedByRule(), src) == []


def test_guarded_by_cross_object_write():
    # the enable_tracing incident: a module function rebuilding a
    # guarded attribute through the global instance
    src = """
    import threading


    class Tracer:
        def __init__(self):
            self._lock = threading.Lock()
            self._ring = []  # guarded-by: _lock


    _global = Tracer()


    def resize_bad(n):
        _global._ring = [None] * n


    def resize_good(n):
        with _global._lock:
            _global._ring = [None] * n
    """
    fs = findings_for(GuardedByRule(), src)
    assert len(fs) == 1
    assert "_global._ring" in fs[0].message


# -- blocking-under-lock ----------------------------------------------------


LOCKED_BODY = """
import threading
import time


class Pool:
    def __init__(self):
        self._lock = threading.Lock()

    def run(self, fut, engine, thread):
        {body}
"""


@pytest.mark.parametrize(
    "body",
    [
        "with self._lock:\n            time.sleep(1.0)",
        "with self._lock:\n            fut.result()",
        "with self._lock:\n            engine.warmup(example=1)",
        "with self._lock:\n            thread.join()",
        (
            "with self._lock:\n"
            "            engines = self.build_replacements(None)"
        ),
    ],
)
def test_blocking_under_lock_fires(body):
    fs = findings_for(
        BlockingUnderLockRule(), LOCKED_BODY.format(body=body)
    )
    assert len(fs) == 1, fs
    assert fs[0].rule == "blocking-under-lock"


@pytest.mark.parametrize(
    "body",
    [
        # the fixed shape: build OUTSIDE, re-point under the lock
        (
            "engines = self.build_replacements(None)\n"
            "        with self._lock:\n"
            "            self.e = engines"
        ),
        "time.sleep(1.0)",                      # no lock held
        "with self._lock:\n            x = ', '.join(['a'])",  # str join
        # Condition.wait releases the lock it waits on
        "with self._lock:\n            self._lock.wait(0.05)",
    ],
)
def test_blocking_under_lock_quiet(body):
    assert findings_for(
        BlockingUnderLockRule(), LOCKED_BODY.format(body=body)
    ) == []


# -- strippable-assert ------------------------------------------------------


def test_strippable_assert_fires_outside_tests():
    fs = findings_for(
        StrippableAssertRule(),
        "def gate(ok):\n    assert ok, 'enforced'\n",
        rel="keystone_tpu/serving/demo_model.py",
    )
    assert len(fs) == 1
    assert fs[0].rule == "strippable-assert"


def test_strippable_assert_quiet_in_tests_and_on_raise():
    assert findings_for(
        StrippableAssertRule(),
        "def test_x():\n    assert 1 == 1\n",
        rel="tests/serving/test_x.py",
    ) == []
    assert findings_for(
        StrippableAssertRule(),
        (
            "def gate(ok):\n"
            "    if not ok:\n"
            "        raise AssertionError('enforced')\n"
        ),
        rel="keystone_tpu/serving/demo_model.py",
    ) == []


# -- absent-not-zero --------------------------------------------------------


def test_absent_not_zero_fires_on_unlabeled_preregistration():
    src = """
    class Metrics:
        def __init__(self, registry):
            self._mfu = registry.gauge(
                "keystone_serving_mfu", "rolling MFU"
            )
    """
    fs = findings_for(AbsentNotZeroRule(), src)
    assert len(fs) == 1
    assert "pre-registered" in fs[0].message


def test_absent_not_zero_quiet_on_labeled_or_lazy_registration():
    src = """
    class Metrics:
        def __init__(self, registry):
            self._mem = registry.gauge(
                "keystone_device_memory_bytes", "hbm",
                ("device", "kind", "stat"),
            )

        def on_available(self, registry):
            self._mfu = registry.gauge("keystone_serving_mfu", "mfu")
    """
    assert findings_for(AbsentNotZeroRule(), src) == []


def test_absent_not_zero_fires_on_zero_stamp():
    src = """
    def degrade(self):
        self.mfu_gauge.set(0)
    """
    fs = findings_for(AbsentNotZeroRule(), src)
    assert len(fs) == 1
    assert "literal 0" in fs[0].message


def test_absent_not_zero_quiet_on_real_zero():
    # staging bytes: an empty pool is a measured zero, not an unknown
    assert findings_for(
        AbsentNotZeroRule(),
        "def on_swap(self):\n    old.metrics.set_staging_bytes(0)\n",
    ) == []


def test_absent_not_zero_fires_on_none_fallback_emission():
    src = """
    def families(m, mfu):
        return MetricFamily(
            "keystone_serving_mfu", "gauge", "mfu",
            [Sample("", {}, mfu if mfu is not None else 0.0)],
        )
    """
    fs = findings_for(AbsentNotZeroRule(), src)
    assert len(fs) == 1
    assert "zero fallback" in fs[0].message


def test_absent_not_zero_fires_on_inverted_none_fallback():
    # the same defect spelled the other way round must not slip by
    src = """
    def families(m, mfu):
        return MetricFamily(
            "keystone_serving_mfu", "gauge", "mfu",
            [Sample("", {}, 0.0 if mfu is None else mfu)],
        )
    """
    fs = findings_for(AbsentNotZeroRule(), src)
    assert len(fs) == 1
    assert "zero fallback" in fs[0].message


def test_absent_not_zero_quiet_on_one_hot_emission():
    # `1.0 if side == r else 0.0` is a one-hot value, not an absence
    # fallback — the real roofline emission must stay clean
    src = """
    def families(m, r):
        return MetricFamily(
            "keystone_device_roofline_bound", "gauge", "side",
            [Sample("", {}, 1.0 if "compute" == r else 0.0)],
        )
    """
    assert findings_for(AbsentNotZeroRule(), src) == []


# -- hot-path-host-sync -----------------------------------------------------


HOT_MODULES = {
    "hot/engine.py": {"gather_once"},
}


@pytest.mark.parametrize(
    "body",
    [
        "y = float(x)",
        "y = x.item()",
        "y = np.asarray(x)",
        "for i, f in enumerate(futs):\n        f.set_result(x[i])",
    ],
)
def test_host_sync_fires_in_hot_module(body):
    src = f"import numpy as np\n\n\ndef deliver(x, futs):\n    {body}\n"
    fs = findings_for(
        HotPathHostSyncRule(modules=HOT_MODULES), src,
        rel="hot/engine.py",
    )
    assert len(fs) == 1, fs
    assert fs[0].rule == "hot-path-host-sync"


def test_host_sync_quiet_on_allowlisted_point_and_cold_modules():
    src = (
        "import numpy as np\n\n\n"
        "def gather_once(x, futs):\n"
        "    host = np.asarray(x)\n"
        "    for i, f in enumerate(futs):\n"
        "        f.set_result(host[i])\n"
    )
    # allowlisted gather point in the hot module: quiet
    assert findings_for(
        HotPathHostSyncRule(modules=HOT_MODULES), src,
        rel="hot/engine.py",
    ) == []
    # same code outside the designated modules: not in scope
    assert findings_for(
        HotPathHostSyncRule(modules=HOT_MODULES), src,
        rel="cold/util.py",
    ) == []


def test_host_sync_quiet_on_float_of_literal_and_dict_lookup():
    src = (
        "def warm(self, want):\n"
        "    x = float('nan')\n"
        "    for b in want:\n"
        "        self._aot[b] = {}\n"
        "        r = self._aot[b]\n"
    )
    assert findings_for(
        HotPathHostSyncRule(modules=HOT_MODULES), src,
        rel="hot/engine.py",
    ) == []


# -- fault-point-drift ------------------------------------------------------


def drift_project(
    tmp_path,
    catalog=("a.point", "b.point"),
    wired=("a.point", "b.point"),
    readme=("a.point", "b.point"),
    tested=("a.point", "b.point"),
):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    entries = ",\n".join(f'    "{p}": "doc"' for p in catalog)
    (pkg / "faults.py").write_text(
        "FAULT_POINTS = {\n" + entries + ",\n}\n"
    )
    calls = "\n".join(
        f'    fire("{p}", None)' for p in wired
    ) or "    pass"
    (pkg / "hot.py").write_text(
        "from pkg.faults import FAULT_POINTS\n\n\n"
        "def fire(p, ctx):\n    return None\n\n\n"
        "def serve():\n" + calls + "\n"
    )
    rows = "\n".join(f"| `{p}` | effect |" for p in readme)
    (tmp_path / "README.md").write_text(
        "# demo\n\n**Fault-point catalog** table:\n\n"
        "| point | effect |\n|---|---|\n" + rows + "\n\n## Next\n"
    )
    tests = tmp_path / "tests"
    tests.mkdir()
    body = "\n".join(f'    arm("{p}")' for p in tested) or "    pass"
    (tests / "test_chaos.py").write_text(
        "def test_points():\n" + body + "\n"
    )
    return FaultPointDriftRule(
        faults_rel="pkg/faults.py",
        readme_rel="README.md",
        tests_rel="tests",
        package_rel="pkg",
    )


def run_drift(tmp_path, rule):
    result = run_analysis(str(tmp_path), ["pkg"], [rule])
    return [f for f in result.findings if f.rule == "fault-point-drift"]


def test_drift_quiet_when_all_four_agree(tmp_path):
    rule = drift_project(tmp_path)
    assert run_drift(tmp_path, rule) == []


def test_drift_fires_on_readme_missing_point(tmp_path):
    rule = drift_project(tmp_path, readme=("a.point",))
    fs = run_drift(tmp_path, rule)
    assert len(fs) == 1 and "missing from the README" in fs[0].message


def test_drift_fires_on_readme_phantom_point(tmp_path):
    rule = drift_project(
        tmp_path, readme=("a.point", "b.point", "ghost.point")
    )
    fs = run_drift(tmp_path, rule)
    assert len(fs) == 1 and "does not catalog" in fs[0].message


def test_drift_fires_on_unwired_catalog_point(tmp_path):
    rule = drift_project(tmp_path, wired=("a.point",))
    fs = run_drift(tmp_path, rule)
    assert len(fs) == 1 and "no `fire(...)`" in fs[0].message
    assert fs[0].path == "pkg/faults.py"


def test_drift_fires_on_untested_point(tmp_path):
    rule = drift_project(tmp_path, tested=("a.point",))
    fs = run_drift(tmp_path, rule)
    assert len(fs) == 1 and "nowhere under tests/" in fs[0].message


def test_drift_fires_on_wired_uncataloged_point(tmp_path):
    rule = drift_project(
        tmp_path, wired=("a.point", "b.point", "rogue.point")
    )
    fs = run_drift(tmp_path, rule)
    assert len(fs) == 1 and "missing from FAULT_POINTS" in fs[0].message
    assert fs[0].path == "pkg/hot.py"


def test_drift_project_scan_survives_file_slices(tmp_path):
    # a --changed-only-style slice (faults.py only) must still see the
    # call sites in the unchanged files — the wired scan reads the
    # whole package from disk, not the analysis slice
    rule = drift_project(tmp_path)
    result = run_analysis(
        str(tmp_path), ["pkg/faults.py"], [rule]
    )
    assert [
        f for f in result.findings if f.rule == "fault-point-drift"
    ] == []


# -- metric-family-drift ----------------------------------------------------


def family_project(
    tmp_path,
    registered=("keystone_demo_hits_total", "keystone_demo_depth"),
    fstring_field=None,
    readme=("keystone_demo_hits_total", "keystone_demo_depth"),
    with_table=True,
):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    lines = ["reg = object()\n\n\ndef wire(reg):"]
    for fam in registered:
        lines.append(f'    reg.counter("{fam}", "help")')
    if fstring_field:
        lines.append(
            f'    reg.gauge(f"keystone_demo_{{{fstring_field}}}_total",'
            ' "help")'
        )
    if len(lines) == 1:
        lines.append("    pass")
    (pkg / "metrics.py").write_text("\n".join(lines) + "\n")
    if with_table:
        rows = "\n".join(f"| `{f}` | counter | doc |" for f in readme)
        (tmp_path / "README.md").write_text(
            "# demo\n\n**Metric-family catalog** — every exported "
            "family:\n\n| family | kind | meaning |\n|---|---|---|\n"
            + rows + "\n\n## Next\n"
        )
    else:
        (tmp_path / "README.md").write_text("# demo\n\nno table here\n")
    return MetricFamilyDriftRule(
        readme_rel="README.md", package_rel="pkg"
    )


def run_family(tmp_path, rule, paths=("pkg",)):
    result = run_analysis(str(tmp_path), list(paths), [rule])
    return [
        f for f in result.findings if f.rule == "metric-family-drift"
    ]


def test_family_quiet_when_code_and_readme_agree(tmp_path):
    rule = family_project(tmp_path)
    assert run_family(tmp_path, rule) == []


def test_family_fires_on_undocumented_registration(tmp_path):
    rule = family_project(tmp_path, readme=("keystone_demo_depth",))
    fs = run_family(tmp_path, rule)
    assert len(fs) == 1
    assert "keystone_demo_hits_total" in fs[0].message
    assert "missing from the README" in fs[0].message
    assert fs[0].path == "README.md"


def test_family_fires_on_phantom_readme_row(tmp_path):
    rule = family_project(
        tmp_path,
        readme=(
            "keystone_demo_hits_total", "keystone_demo_depth",
            "keystone_demo_ghost",
        ),
    )
    fs = run_family(tmp_path, rule)
    assert len(fs) == 1
    assert "nothing in the package registers" in fs[0].message


def test_family_fires_when_table_missing_entirely(tmp_path):
    rule = family_project(tmp_path, with_table=False)
    fs = run_family(tmp_path, rule)
    assert len(fs) == 1 and "no 'Metric-family catalog'" in fs[0].message


def test_family_fstring_pattern_matches_rows(tmp_path):
    # an f-string family covers every row its wildcard matches: the
    # rows are neither phantom nor is the pattern unmatched
    rule = family_project(
        tmp_path,
        registered=(),
        fstring_field="field",
        readme=(
            "keystone_demo_device_seconds_total",
            "keystone_demo_h2d_bytes_total",
        ),
    )
    assert run_family(tmp_path, rule) == []


def test_family_fstring_pattern_unmatched_fires(tmp_path):
    rule = family_project(
        tmp_path, registered=(), fstring_field="field", readme=()
    )
    fs = run_family(tmp_path, rule)
    assert len(fs) == 1
    assert "matches no row" in fs[0].message
    assert fs[0].path == "pkg/metrics.py"


def test_family_scan_survives_file_slices(tmp_path):
    # slicing the analysis to one unrelated file must not hide the
    # registrations in metrics.py — the scan reads the package from
    # disk like the fault-point rule
    rule = family_project(tmp_path)
    (tmp_path / "pkg" / "other.py").write_text("x = 1\n")
    assert run_family(tmp_path, rule, paths=("pkg/other.py",)) == []
