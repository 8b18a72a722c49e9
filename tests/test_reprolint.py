"""tools/reprolint: each rule on a firing, a clean, and a waived snippet,
plus the waiver framework and the dynamic trace audit (tier 1 — the CI
gate is only trustworthy if the analyzers themselves are pinned by tests).
"""
import textwrap

import pytest

from tools.reprolint.config import Config, LockContract
from tools.reprolint.framework import FileContext
from tools.reprolint.rules.hostsync import HostSyncRule
from tools.reprolint.rules.lockdiscipline import LockDisciplineRule
from tools.reprolint.rules.retrace import RetraceRule
from tools.reprolint.rules.vmem import VmemBudgetRule
from tools.reprolint.trace_audit import assert_max_traces

HOT = "src/repro/serve/svc.py"        # matches hot_path_globs
KERNEL = "src/repro/kernels/x/kernel.py"  # matches kernel_globs


def run_rule(rule, path, src, cfg=None):
    ctx = FileContext(path, textwrap.dedent(src), cfg or Config())
    return rule.check(ctx)


def unwaived(findings):
    return [f for f in findings if not f.waived]


# -- retrace -----------------------------------------------------------------


def test_retrace_fires_on_local_jit_and_closure_array():
    src = """
    def serve(x):
        w = np.zeros((4,))
        def f(y):
            return y + w
        return jax.jit(f)(x)
    """
    found = run_rule(RetraceRule(), HOT, src)
    msgs = " ".join(f.message for f in found)
    assert any("locally-defined" in f.message for f in found)
    assert "captures array 'w'" in msgs


def test_retrace_fires_on_jit_in_loop():
    src = """
    def serve(fns, x):
        outs = []
        for f in fns:
            outs.append(jax.jit(f)(x))
        return outs
    """
    found = run_rule(RetraceRule(), HOT, src)
    assert any("loop" in f.message for f in found)


def test_retrace_clean_on_module_scope_and_builders():
    src = """
    def _impl(x):
        return x * 2

    top = jax.jit(_impl)

    def make_search(index):
        def f(q):
            return q @ index
        return jax.jit(f)
    """
    assert run_rule(RetraceRule(), HOT, src) == []


def test_retrace_waived():
    src = """
    def serve(x):
        def f(y):
            return y * 2
        return jax.jit(f)(x)  # reprolint: disable=retrace
    """
    found = run_rule(RetraceRule(), HOT, src)
    assert found and all(f.waived for f in found)


# -- vmem --------------------------------------------------------------------

_KERNEL_TMPL = """
def mykernel(x, bq=None):
    bq = bq or {bq}
    return pl.pallas_call(
        _kern,
        in_specs=[pl.BlockSpec((bq, {bn}), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bq, {bn}), lambda i: (i, 0)),
    )(x)
"""


def test_vmem_fires_over_budget():
    # 1024*4096*4B = 16 MiB per spec, x2 specs x2 double-buffer = 64 MiB.
    src = _KERNEL_TMPL.format(bq=1024, bn=4096)
    found = run_rule(VmemBudgetRule(), KERNEL, src)
    assert len(found) == 1
    assert "exceeds" in found[0].message
    assert "64.00 MiB" in found[0].message


def test_vmem_clean_under_budget_and_non_kernel_paths_skipped():
    src = _KERNEL_TMPL.format(bq=128, bn=512)
    assert run_rule(VmemBudgetRule(), KERNEL, src) == []
    big = _KERNEL_TMPL.format(bq=1024, bn=4096)
    assert run_rule(VmemBudgetRule(), HOT, big) == []  # not a kernel file


def test_vmem_unbounded_dim_is_a_finding():
    src = """
    def mykernel(x, mystery):
        return pl.pallas_call(
            _kern,
            in_specs=[pl.BlockSpec((mystery, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
        )(x)
    """
    found = run_rule(VmemBudgetRule(), KERNEL, src)
    assert any("cannot bound" in f.message for f in found)


def test_vmem_evaluator_tile_clamps_and_scratch():
    # min() clamp + round_up + or-default, plus a VMEM scratch allocation:
    # bq = min(1024 or 1024, round_up(9, 8)=16) -> 16; blocks 2*16*128*4B
    # = 16 KiB -> x2 = 32 KiB; scratch 16*128*4 = 8 KiB.  Budget 64 KiB
    # passes; 32 KiB fails (proves the estimate tracks the clamped tile).
    src = """
    def mykernel(x, bq=None):
        b = 9
        bq = min(bq or 1024, common.round_up(b, 8))
        return pl.pallas_call(
            _kern,
            in_specs=[pl.BlockSpec((bq, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((bq, 128), lambda i: (i, 0)),
            scratch_shapes=[pltpu.MemorySpace.VMEM((bq, 128), jnp.float32)],
        )(x)
    """
    cfg_pass = Config(vmem_budget_bytes=64 * 1024)
    cfg_fail = Config(vmem_budget_bytes=32 * 1024)
    assert run_rule(VmemBudgetRule(), KERNEL, src, cfg_pass) == []
    found = run_rule(VmemBudgetRule(), KERNEL, src, cfg_fail)
    assert len(found) == 1 and "0.04 MiB" in found[0].message


def test_vmem_waived():
    src = """
    # reprolint: disable=vmem
    def mykernel(x, bq=None):
        bq = bq or 1024
        return pl.pallas_call(
            _kern,
            in_specs=[pl.BlockSpec((bq, 4096), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((bq, 4096), lambda i: (i, 0)),
        )(x)
    """
    found = run_rule(VmemBudgetRule(), KERNEL, src)
    assert found and all(f.waived for f in found)


# -- hostsync ----------------------------------------------------------------


def test_hostsync_fires_on_item_float_and_asarray():
    src = """
    def serve(x, scores):
        a = x.item()
        b = float(scores)
        c = np.asarray(scores)
        return a + b, c
    """
    found = run_rule(HostSyncRule(), HOT, src)
    assert len(found) == 3


def test_hostsync_clean_forms():
    src = """
    V = np.asarray(RAW_TABLE)  # module scope: import-time is not hot

    def serve(x, q):
        n = len(q)
        m = int(x.shape[0])
        lst = np.array([r is not None for r in q])
        t = float(time.perf_counter())
        return n + m, lst, t
    """
    assert run_rule(HostSyncRule(), HOT, src) == []


def test_hostsync_matcher_call_scope():
    src = """
    class FooMatcher:
        def __call__(self, q):
            return q.item()

    class Helper:
        def __call__(self, q):
            return q.item()

    def free(q):
        return q.item()
    """
    found = run_rule(HostSyncRule(), "src/repro/core/pipeline.py", src)
    # only the matcher-class __call__ is hot in pipeline.py
    assert len(found) == 1
    assert found[0].line == 4  # FooMatcher.__call__'s body


def test_hostsync_waived():
    src = """
    def serve(x):
        return x.item()  # reprolint: disable=hostsync
    """
    found = run_rule(HostSyncRule(), HOT, src)
    assert found and all(f.waived for f in found)


# -- lockdiscipline ----------------------------------------------------------

_CONTRACT = Config(lock_contracts=(
    LockContract(
        path_glob="src/x.py", class_name="Svc", lock_attr="_lock",
        worker_entries=("_loop",), exempt_methods=("__init__",),
        threadsafe_attrs=("_queue",),
    ),
))

_SVC_TMPL = """
class Svc:
    def __init__(self):
        self._lock = threading.RLock()
        self.count = 0
        self.ring = []

    def _loop(self):
        {worker_body}

    def caller(self):
        {caller_body}

    def locked_caller(self):
        with self._lock:
            self._sink(1)

    def _sink(self, v):
        self.ring.append(v)
"""


def _svc(worker_body, caller_body):
    return _SVC_TMPL.format(worker_body=worker_body, caller_body=caller_body)


def test_lockdiscipline_fires_on_unlocked_mutations():
    src = _svc("self.count += 1\n        self._sink(2)",
               "self.count += 1")
    found = run_rule(LockDisciplineRule(), "src/x.py", src, _CONTRACT)
    # worker bumps count off-lock; caller bumps count off-lock.  _sink is
    # NOT lock-held (one of its call sites is the unlocked worker), so its
    # ring.append is an off-lock worker-reachable mutation too.
    lines = {f.line for f in found}
    assert len(found) == 3
    assert any("worker thread" in f.message for f in found)
    assert any("caller threads" in f.message for f in found)
    assert lines  # every finding carries a real location


def test_lockdiscipline_clean_with_lock_and_helper_propagation():
    src = _svc(
        "with self._lock:\n            self.count += 1",
        "with self._lock:\n            self.count += 1",
    )
    # _sink's only call site is locked_caller's with-block -> lock-held.
    assert run_rule(LockDisciplineRule(), "src/x.py", src, _CONTRACT) == []


def test_lockdiscipline_threadsafe_attrs_exempt():
    src = _svc("self._queue.put(1)", "pass")
    assert run_rule(LockDisciplineRule(), "src/x.py", src, _CONTRACT) == []


def test_lockdiscipline_waived():
    src = _svc("self.count += 1  # reprolint: disable=lockdiscipline",
               "pass")
    found = run_rule(LockDisciplineRule(), "src/x.py", src, _CONTRACT)
    assert found and all(f.waived for f in found)


# -- waiver framework --------------------------------------------------------


def test_scope_waiver_covers_whole_function():
    src = """
    # reprolint: disable=hostsync
    def serve(x):
        a = x.item()
        return float(a)
    """
    found = run_rule(HostSyncRule(), HOT, src)
    assert len(found) == 2 and all(f.waived for f in found)


def test_waiver_trailing_prose_and_multi_rule():
    src = """
    def serve(x):
        a = x.item()  # reprolint: disable=hostsync, retrace  hand-off point
        return a
    """
    found = run_rule(HostSyncRule(), HOT, src)
    assert found and all(f.waived for f in found)


def test_waived_findings_stay_visible():
    """A waiver must never make a finding disappear entirely — stale
    waivers are caught in review because the finding still reports."""
    src = """
    def serve(x):
        return x.item()  # reprolint: disable=hostsync
    """
    found = run_rule(HostSyncRule(), HOT, src)
    assert len(found) == 1
    assert found[0].waived and "item" in found[0].message


# -- dynamic trace audit -----------------------------------------------------


def test_assert_max_traces_flags_fresh_compiles():
    import jax
    import jax.numpy as jnp

    with pytest.raises(AssertionError, match="backend compile"):
        with assert_max_traces(0):
            # a brand-new jitted callable always reaches the backend
            jax.jit(lambda x: x * 3.0 + 41.5)(jnp.ones((3,)))


def test_assert_max_traces_passes_on_cache_hits():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2.0 - 7.25)
    x = jnp.ones((4,))
    f(x)  # warm
    with assert_max_traces(0) as audit:
        for _ in range(5):
            f(x)
    assert audit.compiles == 0
