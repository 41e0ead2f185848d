"""Tier-1 tests for repro-lint (:mod:`repro.analysis`).

Three layers:

* per-rule fixtures — every rule gets a positive (violation fires), a
  negative (idiomatic zone code stays clean), and a suppression case;
* a regression fixture reproducing the real ``weighted_distance``
  iter-order violation fixed in the same PR that introduced the linter;
* the tree gate — ``src``/``benchmarks``/``examples`` must lint clean, so
  any new determinism hazard fails tier-1 before it can ship.
"""

from __future__ import annotations

import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis.lint import lint_paths, lint_source, main
from repro.analysis.rules import RULES, Violation
from repro.analysis.zones import rules_for_path

REPO_ROOT = Path(__file__).resolve().parent.parent

# Synthetic paths that land in each zone (zone matching is purely textual).
CORE = "src/repro/core/fixture_mod.py"
HOT = "src/repro/core/search/fixture_mod.py"
HARNESS = "benchmarks/fixture_bench.py"
KERNEL = "src/repro/kernels/fixture_kernel.py"  # accelerator kernels (f32 ok)
SEARCH_KERNEL = "src/repro/core/search/kernels/fixture_kernel.py"
DES = "src/repro/stream/des/fixture_engine.py"
OBS = "src/repro/obs/fixture_obs.py"
OUTSIDE = "tools/fixture_tool.py"


def rules_hit(source: str, path: str = CORE):
    violations, _ = lint_source(textwrap.dedent(source), path)
    return {v.rule for v in violations}


def violations_of(source: str, path: str = CORE):
    violations, _ = lint_source(textwrap.dedent(source), path)
    return violations


# --------------------------------------------------------------------------
# zones
# --------------------------------------------------------------------------


def test_zone_rule_sets():
    core = set(rules_for_path(CORE))
    hot = set(rules_for_path(HOT))
    harness = set(rules_for_path(HARNESS))
    kernel = set(rules_for_path(KERNEL))
    skernel = set(rules_for_path(SEARCH_KERNEL))
    assert "iter-order" in core and "hot-loop" not in core
    assert {"hot-loop", "float32-literal", "iter-order"} <= hot
    assert "unseeded-random" in harness and "hot-loop" not in harness
    # Accelerator kernels: pallas hygiene, but no exactness dtype pinning
    # (the flash kernels are float32 by design) and no hot-loop zone.
    assert {"pallas-interpret", "pallas-accum-order", "pallas-grid-truncate"} <= kernel
    assert "pallas-accum-dtype" not in kernel
    assert "float32-literal" not in kernel
    # Search kernels: everything above PLUS the golden-oracle exactness
    # contract (float64 accumulators) and the hot-loop/search-zone rules,
    # because repro/core/search/kernels nests inside repro/core/search.
    assert {
        "pallas-interpret",
        "pallas-accum-order",
        "pallas-grid-truncate",
        "pallas-accum-dtype",
        "float32-literal",
        "hot-loop",
    } <= skernel
    assert rules_for_path(OUTSIDE) == ()
    # The DES executor is core-zone: its bit-identical-trace contract means
    # every random draw must flow from a seeded Philox root, and none of the
    # hot-loop/kernel rules apply (it's a pure-Python event loop).
    des = set(rules_for_path(DES))
    assert des == core
    assert "hot-loop" not in des and "pallas-interpret" not in des
    # The observability plane: byte-identical-JSONL contract => core
    # determinism rules, plus hot-loop so wall-clock reads stay confined
    # to the single allow-listed shim in obs/clock.py.  No jax in obs.
    obs = set(rules_for_path(OBS))
    assert {
        "unseeded-random",
        "iter-order",
        "float-sum",
        "np-reduce-dtype",
        "hot-loop",
    } == obs
    assert "jax-purity" not in obs and "float32-literal" not in obs


def test_des_zone_catches_unseeded_stream():
    # An unseeded default_rng() in the DES would silently break the
    # fixed-seed -> bit-identical-trace determinism contract.
    src = """
        import numpy as np
        def service_time(mean):
            rng = np.random.default_rng()
            return rng.exponential(mean)
    """
    assert "unseeded-random" in rules_hit(src, DES)
    seeded = """
        import numpy as np
        def service_stream(seed):
            return np.random.Generator(np.random.Philox([seed, 0x5E21CE]))
    """
    assert "unseeded-random" not in rules_hit(seeded, DES)


def test_obs_zone_catches_wall_clock_read():
    # A bare wall-clock read in the telemetry plane would leak wall time
    # into exported metrics and break the byte-identical-JSONL goldens.
    src = """
        import time
        def span_duration(t_enter):
            return time.perf_counter() - t_enter
    """
    assert "hot-loop" in rules_hit(src, OBS)
    # ...and the sanctioned shim pattern: a same-line justified allow, which
    # is exactly how obs/clock.py confines the tree's one wall-clock site.
    shim = (
        "import time\n"
        "def perf_counter():\n"
        "    return time.perf_counter()  # repro-lint: allow(hot-loop) shim\n"
    )
    kept, suppressed = lint_source(shim, OBS)
    assert kept == []
    assert [v.rule for v in suppressed] == ["hot-loop"]


def test_obs_zone_catches_float_sum_and_unseeded_random():
    src = """
        import numpy as np
        def summarize(values):
            rng = np.random.default_rng()
            return sum(values), rng
    """
    assert rules_hit(src, OBS) == {"float-sum", "unseeded-random"}


def test_outside_zone_is_never_linted():
    assert violations_of("import random\nrandom.random()\n", OUTSIDE) == []


def test_all_registered_rules_are_reachable_from_some_zone():
    reachable = (
        set(rules_for_path(CORE))
        | set(rules_for_path(HOT))
        | set(rules_for_path(HARNESS))
        | set(rules_for_path(KERNEL))
        | set(rules_for_path(SEARCH_KERNEL))
        | set(rules_for_path(OBS))
    )
    assert reachable == set(RULES)


# --------------------------------------------------------------------------
# unseeded-random
# --------------------------------------------------------------------------


def test_unseeded_random_positive():
    src = """
    import random
    import numpy as np

    def jitter(xs):
        np.random.shuffle(xs)
        k = random.choice(xs)
        rng = np.random.default_rng()
        return k, rng
    """
    vs = violations_of(src)
    assert [v.rule for v in vs] == ["unseeded-random"] * 3


def test_unseeded_random_negative():
    src = """
    import random
    import numpy as np

    def jitter(xs, seed):
        rng = np.random.Generator(np.random.Philox(seed))
        alt = np.random.default_rng(seed)
        py = random.Random(seed)
        return rng.permutation(xs), alt, py
    """
    assert rules_hit(src) == set()


def test_unseeded_random_suppressed():
    src = """
    import numpy as np

    rng = np.random.default_rng()  # repro-lint: allow(unseeded-random) demo only
    """
    kept, suppressed = lint_source(textwrap.dedent(src), CORE)
    assert kept == []
    assert [v.rule for v in suppressed] == ["unseeded-random"]


# --------------------------------------------------------------------------
# iter-order
# --------------------------------------------------------------------------


def test_iter_order_positive_for_loop_and_reductions():
    src = """
    def f(rv, members):
        acc = 0.0
        for d in rv.dims:          # set-valued attribute
            acc += rv[d]
        s = {1.0, 2.0}
        order = list(s)            # order-sensitive builtin over a set
        total = sum(x * x for x in s)
        table = {d: rv[d] for d in rv.soft_dims}
        return acc, order, total, table
    """
    vs = violations_of(src)
    assert {v.rule for v in vs} == {"iter-order"}
    assert len(vs) == 4


def test_iter_order_tracks_set_algebra_and_dict_of_sets():
    src = """
    def f(topology, hosts):
        upstream_of = {c: set(topology.upstream(c)) for c in topology.components}
        for up in upstream_of.get("b", ()):
            hosts[up] = True
        combined = upstream_of["a"] | {"x"}
        return [hosts[u] for u in combined]
    """
    vs = violations_of(src)
    assert [v.rule for v in vs] == ["iter-order"] * 2


def test_iter_order_negative_sorted_and_order_free_consumers():
    src = """
    def f(rv, demand):
        total = sum(rv[d] for d in sorted(rv.dims))
        ok = all(rv[d] >= demand[d] for d in demand.hard)
        n = len({d for d in rv.dims if rv[d] > 0})
        cols = sorted(rv[d] for d in rv.hard)
        for d in sorted(demand.dims | rv.dims):
            total += demand[d]
        return total, ok, n, cols
    """
    assert rules_hit(src) == set()


def test_iter_order_local_self_assignment_beats_zone_set_attrs():
    # PlacementArena binds self.dims to a *sorted list*; the zone-wide
    # "dims is a frozenset" fact must not apply to it.
    src = """
    class Arena:
        def __init__(self, dims):
            self.dims = sorted(dims)

        def weight_row(self, merged):
            return [merged.get(d, 1.0) for d in self.dims]
    """
    assert rules_hit(src) == set()


def test_iter_order_suppressed_by_comment_line_above():
    src = """
    def f(s):
        # repro-lint: allow(iter-order) order feeds a set, not floats
        # (multi-line justification keeps the suppression attached)
        return [x for x in s if x]

    def g():
        s = set("abc")
        return f(s)
    """
    kept, suppressed = lint_source(textwrap.dedent(src), CORE)
    assert kept == []
    assert suppressed == []  # `s` param type unknown inside f — nothing fires
    src2 = """
    s = set("abc")
    # repro-lint: allow(iter-order) demo
    # justification continues here
    order = list(s)
    """
    kept2, suppressed2 = lint_source(textwrap.dedent(src2), CORE)
    assert kept2 == []
    assert [v.rule for v in suppressed2] == ["iter-order"]


def test_wrong_rule_name_does_not_suppress():
    src = """
    s = {1, 2}
    order = list(s)  # repro-lint: allow(float-sum) wrong rule
    """
    kept, _ = lint_source(textwrap.dedent(src), CORE)
    assert [v.rule for v in kept] == ["iter-order"]


def test_wildcard_suppression():
    src = """
    s = {1, 2}
    order = list(s)  # repro-lint: allow(*) fixture
    """
    kept, suppressed = lint_source(textwrap.dedent(src), CORE)
    assert kept == [] and len(suppressed) == 1


# --------------------------------------------------------------------------
# float-sum / np-reduce-dtype / float32-literal
# --------------------------------------------------------------------------


def test_float_sum_positive_negative():
    bad = "def f(xs):\n    return sum(xs)\n"
    good = "import math\ndef f(xs):\n    return xs.sum() + math.fsum(xs)\n"
    assert rules_hit(bad) == {"float-sum"}
    assert rules_hit(good) == set()


def test_np_reduce_dtype_positive_negative():
    bad = """
    import numpy as np

    def f(a, b):
        return np.sum(a) + np.dot(a, b)
    """
    good = """
    import numpy as np

    def f(a, b):
        return np.sum(a, dtype=np.float64) + a.astype(np.float64) @ b
    """
    assert rules_hit(bad) == {"np-reduce-dtype"}
    assert rules_hit(good) == set()


def test_float32_literal_fires_only_in_hot_zone():
    src = """
    import numpy as np

    def f(n):
        return np.zeros(n, dtype=np.float32)
    """
    assert rules_hit(src, HOT) == {"float32-literal"}
    assert rules_hit(src, CORE) == set()  # core zone does not pin dtypes


def test_float32_dtype_string_in_hot_zone():
    src = """
    import jax.numpy as jnp

    def f(n):
        return jnp.zeros(n, dtype="float32")
    """
    assert rules_hit(src, HOT) == {"float32-literal"}


# --------------------------------------------------------------------------
# jax-purity / x64-scope
# --------------------------------------------------------------------------


def test_jax_purity_positive():
    src = """
    import jax
    import numpy as np

    TRACE_LOG = []
    CACHE = {}

    @jax.jit
    def step(x):
        print("tracing", x)
        y = np.asarray(x)
        TRACE_LOG.append(y)
        CACHE["last"] = y
        return x * 2
    """
    vs = violations_of(src)
    assert [v.rule for v in vs] == ["jax-purity"] * 4


def test_jax_purity_wrapped_call_form():
    src = """
    import jax

    def body(carry, x):
        print(x)
        return carry + x, x

    def run(xs):
        return jax.lax.scan(body, 0.0, xs)
    """
    assert rules_hit(src) == {"jax-purity"}


def test_jax_purity_negative():
    src = """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        scratch = []
        scratch.append(x)          # local mutation is fine
        return jnp.sum(jnp.asarray(scratch[0]))
    """
    assert rules_hit(src) == set()


def test_x64_scope_positive_and_exemption():
    src = """
    import jax

    def force_x64():
        jax.config.update("jax_enable_x64", True)
    """
    assert rules_hit(src, CORE) == {"x64-scope"}
    # The scoped helper module itself is the one allowed owner.
    assert rules_hit(src, "src/repro/core/search/backend.py") == set()


def test_x64_scope_import_form():
    src = "from jax.experimental import enable_x64\n"
    assert rules_hit(src, CORE) == {"x64-scope"}


def test_x64_scope_context_manager_call():
    src = """
    import jax

    def scoped(x):
        with jax.enable_x64(True):
            return x
    """
    assert rules_hit(src, CORE) == {"x64-scope"}
    assert rules_hit(src, HARNESS) == {"x64-scope"}
    assert rules_hit(src, "src/repro/core/search/backend.py") == set()


# --------------------------------------------------------------------------
# hot-loop
# --------------------------------------------------------------------------


def test_hot_loop_positive():
    src = """
    import copy
    import math
    import time

    def anneal_step(state, delta, temp):
        t0 = time.perf_counter()
        trial = copy.deepcopy(state)
        accept = delta < temp * math.exp(-1.0)
        return trial, accept, t0
    """
    vs = violations_of(src, HOT)
    assert [v.rule for v in vs] == ["hot-loop"] * 3


def test_hot_loop_not_active_outside_engine_search():
    # schedulers.py's legacy path may deepcopy — by zone design.
    src = "import copy\ndef f(c):\n    return copy.deepcopy(c)\n"
    assert rules_hit(src, CORE) == set()


def test_hot_loop_threshold_accepting_negative():
    src = """
    def accept(delta, threshold):
        return delta <= threshold  # exact comparison, no libm
    """
    assert rules_hit(src, HOT) == set()


# --------------------------------------------------------------------------
# pallas kernel zone: interpret / accum-order / accum-dtype / grid-truncate
# --------------------------------------------------------------------------


def test_pallas_interpret_positive_negative():
    bad = """
    import jax.experimental.pallas as pl

    def run(x):
        return pl.pallas_call(kernel, out_shape=x, interpret=True)(x)
    """
    good = """
    import jax.experimental.pallas as pl

    def run(x, interpret):
        return pl.pallas_call(kernel, out_shape=x, interpret=interpret)(x)
    """
    assert rules_hit(bad, KERNEL) == {"pallas-interpret"}
    assert rules_hit(good, KERNEL) == set()
    # Wrapper call sites are covered too — forcing interpret on a helper
    # that plumbs the flag is the same hazard.
    wrapper = "def f(ba, P):\n    return fused_score(ba, P, interpret=True)\n"
    assert rules_hit(wrapper, SEARCH_KERNEL) == {"pallas-interpret"}
    # Outside the kernel zones the rule is not active (tests pin
    # interpret=True deliberately — that is the golden-oracle harness).
    assert rules_hit(bad, CORE) == set()


def test_pallas_interpret_suppressed():
    src = """
    def run(x):
        # repro-lint: allow(pallas-interpret) CI smoke leg has no TPU
        return pl.pallas_call(kernel, out_shape=x, interpret=True)(x)
    """
    kept, suppressed = lint_source(textwrap.dedent(src), KERNEL)
    assert kept == []
    assert [v.rule for v in suppressed] == ["pallas-interpret"]


def test_pallas_accum_order_positive_negative():
    bad = """
    def kernel(x_ref, o_ref):
        i = pl.program_id(0)
        o_ref[0] += x_ref[i]
    """
    good = """
    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...].sum()
    """
    assert rules_hit(bad, KERNEL) == {"pallas-accum-order"}
    assert rules_hit(good, KERNEL) == set()


def test_pallas_accum_order_inline_program_id_and_suppression():
    bad = "def kernel(x_ref, o_ref):\n    o_ref[pl.program_id(0)] += 1.0\n"
    assert rules_hit(bad, KERNEL) == {"pallas-accum-order"}
    ok = (
        "def kernel(x_ref, o_ref):\n"
        "    # repro-lint: allow(pallas-accum-order) grid-quantized exact adds\n"
        "    o_ref[pl.program_id(0)] += 1.0\n"
    )
    kept, suppressed = lint_source(ok, KERNEL)
    assert kept == [] and len(suppressed) == 1


def test_pallas_accum_dtype_positive_negative():
    bad = """
    import jax.numpy as jnp
    import numpy as np

    def kernel(x_ref, o_ref):
        acc = jnp.zeros((8, 4))
        out = np.zeros(8, dtype=np.float32)
        return acc, out
    """
    good = """
    import jax.numpy as jnp
    import numpy as np

    def kernel(x_ref, o_ref):
        acc = jnp.zeros((8, 4), dtype=jnp.float64)
        idx = np.zeros(8, np.int32)
        flags = np.full(8, False, dtype=np.bool_)
        return acc, idx, flags
    """
    vs = violations_of(bad, SEARCH_KERNEL)
    # missing dtype (jnp defaults to f32) + explicit f32; the f32 literal
    # also trips the hot-loop zone's float32-literal rule on this path.
    assert {v.rule for v in vs} >= {"pallas-accum-dtype"}
    assert sum(v.rule == "pallas-accum-dtype" for v in vs) == 2
    assert rules_hit(good, SEARCH_KERNEL) == set()
    # The float32 flash kernels are outside the exactness subzone.
    assert "pallas-accum-dtype" not in rules_hit(bad, KERNEL)


def test_pallas_grid_truncate_positive_negative():
    bad = """
    import jax.experimental.pallas as pl

    def run(x, B, blk):
        return pl.pallas_call(kernel, grid=(B // blk,), out_shape=x)(x)
    """
    good = """
    import jax.experimental.pallas as pl

    def run(x, B, blk):
        return pl.pallas_call(kernel, grid=(pl.cdiv(B, blk),), out_shape=x)(x)
    """
    assert rules_hit(bad, KERNEL) == {"pallas-grid-truncate"}
    assert rules_hit(good, KERNEL) == set()
    # Floor division elsewhere in a kernel file is fine — only a
    # pallas_call grid silently drops work.
    other = "def f(n, b):\n    return n // b\n"
    assert rules_hit(other, KERNEL) == set()


# --------------------------------------------------------------------------
# regression: the real weighted_distance violation fixed in this PR
# --------------------------------------------------------------------------

WEIGHTED_DISTANCE_PRE_FIX = """
import math

def weighted_distance(demand, avail, w, network_distance):
    acc = 0.0
    for d in (demand.dims | avail.dims) - {"bandwidth"}:
        acc += w.get(d, 1.0) * (demand[d] - avail[d]) ** 2
    acc += w.get("bandwidth", 1.0) * network_distance ** 2
    return math.sqrt(acc)
"""


def test_regression_weighted_distance_pre_fix_flagged():
    vs = violations_of(WEIGHTED_DISTANCE_PRE_FIX, "src/repro/core/resources.py")
    assert [v.rule for v in vs] == ["iter-order"]
    assert vs[0].line == 6  # the `for d in (... | ...) - {...}` header


def test_regression_weighted_distance_post_fix_clean():
    fixed = WEIGHTED_DISTANCE_PRE_FIX.replace(
        'for d in (demand.dims | avail.dims) - {"bandwidth"}:',
        'for d in sorted((demand.dims | avail.dims) - {"bandwidth"}):',
    )
    assert violations_of(fixed, "src/repro/core/resources.py") == []


# --------------------------------------------------------------------------
# engine mechanics: rendering, ordering, parse errors, CLI, tree gate
# --------------------------------------------------------------------------


def test_violation_render_format():
    v = Violation(path="a/b.py", line=3, col=7, rule="iter-order", message="m")
    assert v.render() == "a/b.py:3:7: iter-order: m"


def test_violations_sorted_by_position():
    src = """
    s = {1, 2}
    b = list(s)
    a = tuple(s)
    """
    vs = violations_of(src)
    assert [v.line for v in vs] == sorted(v.line for v in vs)


def test_parse_error_reported_not_raised():
    kept, _ = lint_source("def broken(:\n", CORE)
    assert [v.rule for v in kept] == ["parse-error"]


def test_cli_clean_dirty_and_missing_path(tmp_path, capsys):
    clean = tmp_path / "src" / "repro" / "core" / "ok.py"
    clean.parent.mkdir(parents=True)
    clean.write_text("x = 1\n", encoding="utf-8")
    dirty = clean.with_name("bad.py")
    dirty.write_text("s = {1, 2}\norder = list(s)\n", encoding="utf-8")

    assert main([str(clean)]) == 0
    rc = main([str(dirty)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "bad.py:2:" in out and "iter-order" in out
    assert main([str(tmp_path / "nope")]) == 2


def test_cli_list_rules(capsys):
    assert main(["--list-rules"]) == 0
    listed = capsys.readouterr().out.split()
    assert set(listed) == set(RULES)


def test_tree_is_clean():
    """The acceptance gate: the real tree has zero unsuppressed violations."""
    roots = [
        str(REPO_ROOT / "src"),
        str(REPO_ROOT / "benchmarks"),
        str(REPO_ROOT / "examples"),
    ]
    violations, _suppressed, n_zone = lint_paths(roots)
    assert violations == [], "\n".join(v.render() for v in violations)
    assert n_zone > 30  # the zones really do cover the tree


def test_module_entrypoint_runs_clean():
    """`python -m repro.analysis.lint` exits 0 on the tree (no runpy warning)."""
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", "src", "benchmarks", "examples"],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        env={**__import__("os").environ, "PYTHONPATH": str(REPO_ROOT / "src")},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "RuntimeWarning" not in proc.stderr
