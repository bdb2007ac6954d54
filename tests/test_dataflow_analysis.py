"""Flow-sensitive dataflow engine + PK/DN/TE/JC families (ISSUE 6).

Fast tier: imports no jax/grpc. Fixture tests prove each family's
positive/negative/suppressed behavior; every family has a seeded RED
test whose finding demonstrably comes from THAT rule (the same source
analyzed with the rule disabled yields nothing) and is not absorbed by
the checked-in baseline; the acceptance test pins flow-sensitivity
strictly beyond PR 5's reachability — PK501 separating two paths
through the same call chain that TS102 (and TS104's sync vocabulary)
cannot tell apart.
"""

import os
import textwrap

from tpushare.analysis import baseline as baseline_mod
from tpushare.analysis import callgraph, dataflow
from tpushare.analysis import load_config
from tpushare.analysis.engine import all_rules, analyze_file, analyze_paths

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "analysis")
CONFIG = load_config(root=REPO)


def rules_of(prefix):
    picked = [r for r in all_rules() if r.id.startswith(prefix)]
    assert picked, f"no rules registered under {prefix}"
    return picked


def rules_except(rule_id):
    return [r for r in all_rules() if r.id != rule_id]


def run_fixture(name, prefix):
    return analyze_file(os.path.join(FIXTURES, name), CONFIG,
                        rules=rules_of(prefix), respect_scope=False)


def run_source(tmp_path, source, rules, name="seeded.py"):
    src = tmp_path / name
    src.write_text(textwrap.dedent(source))
    return analyze_file(str(src), CONFIG, rules=rules,
                        respect_scope=False)


# ---------------------------------------------------------------------------
# PK501 / PK502 — key lineage
# ---------------------------------------------------------------------------

def test_pk_positives():
    found = run_fixture("pk_positive.py", "PK")
    pk501 = [f for f in found if f.rule == "PK501"]
    pk502 = [f for f in found if f.rule == "PK502"]
    assert len(pk501) == 6, found
    assert len(pk502) == 2, found
    msgs = " ".join(f.message for f in pk501)
    assert "along another branch" in msgs      # the branch-path shape
    assert "'ks[0]'" in msgs                   # container cell reuse
    assert "'k'" in msgs                       # alias reuse
    msgs2 = " ".join(f.message for f in pk502)
    assert "retired by the split" in msgs2


def test_pk_negatives():
    assert run_fixture("pk_negative.py", "PK") == []


def test_pk_suppressed():
    assert run_fixture("pk_suppressed.py", "PK") == []


def test_pk501_flow_sensitivity_beyond_ts102_and_ts104(tmp_path):
    """THE acceptance pin: two paths through the same call chain —
    one clean, one reusing the key via a helper — distinguished by
    PK501 and invisible to TS102 (intersection join, bare names only,
    no chains) and to TS104 (sync vocabulary, not key lineage)."""
    source = """
        import jax

        def consume(key):
            return jax.random.uniform(key, (2,))

        def tick(rng, cold):
            if cold:
                a = consume(rng)            # consumes rng on this path
            else:
                a = jax.random.normal(jax.random.fold_in(rng, 7), (2,))
            return a + jax.random.normal(rng, (2,))   # reuse on ONE path
        """
    pk = run_source(tmp_path, source, rules_of("PK501"))
    assert len(pk) == 1, pk
    assert pk[0].rule == "PK501"
    assert "along another branch" in pk[0].message
    # the clean path must NOT flag: the same source with the branch
    # always taking the fold_in arm is silent
    clean = source.replace("a = consume(rng)",
                           "a = jax.random.normal("
                           "jax.random.fold_in(rng, 1), (2,))")
    assert run_source(tmp_path, clean, rules_of("PK501"),
                      name="clean.py") == []
    # TS102 and TS104 both blind to it
    assert run_source(tmp_path, source, rules_of("TS102"),
                      name="b.py") == []
    assert run_source(tmp_path, source, rules_of("TS104"),
                      name="c.py") == []


def test_pk501_red_seeded_interprocedural_not_absorbed(tmp_path):
    """Red test: the reuse is only visible through the callee's
    key-consumption summary. Disabling PK501 proves the finding is
    the rule's; the checked-in baseline absorbs none of it."""
    source = """
        import jax

        class SamplerSlotServer:
            def _draw(self, key, shape):
                return jax.random.normal(key, shape)

            def _spec_step(self, rng):
                drafts = self._draw(rng, (4,))
                accept = self._draw(rng, (4,))    # summary-reached reuse
                return drafts, accept
        """
    found = run_source(tmp_path, source, rules_of("PK501"))
    assert len(found) == 1
    assert "PK501" == found[0].rule
    assert run_source(tmp_path, source, rules_except("PK501"),
                      name="off.py") == []
    entries = baseline_mod.load(CONFIG.resolve(CONFIG.baseline))
    new, _ = baseline_mod.diff(found, entries)
    assert len(new) == 1


def test_pk502_red_dropped_split_not_absorbed(tmp_path):
    found = run_source(tmp_path, """
        import jax

        def admit(rng):
            jax.random.split(rng)               # children dropped
            return jax.random.normal(rng, (2,))
        """, rules_of("PK502"))
    assert len(found) == 1 and found[0].rule == "PK502"
    entries = baseline_mod.load(CONFIG.resolve(CONFIG.baseline))
    new, _ = baseline_mod.diff(found, entries)
    assert len(new) == 1


def test_ts102_fallback_partition(tmp_path):
    """Every flow is owned by exactly one rule: resolvable functions
    by PK501 (TS102 silent), global-rebinding functions by TS102 (PK
    silent) — never zero, never two."""
    source = """
        import jax

        _K = None

        def unresolvable():
            global _K
            _K = jax.random.PRNGKey(0)
            a = jax.random.normal(_K, (2,))
            return a + jax.random.uniform(_K, (2,))

        def resolvable(rng):
            a = jax.random.normal(rng, (2,))
            return a + jax.random.uniform(rng, (2,))
        """
    ts = run_source(tmp_path, source, rules_of("TS102"))
    pk = run_source(tmp_path, source, rules_of("PK501"), name="p.py")
    assert len(ts) == 1 and "unresolvable" not in ts[0].message
    assert ts[0].line < pk[0].line     # TS102 hit is in unresolvable()
    assert len(pk) == 1


# ---------------------------------------------------------------------------
# DN601 / DN602 — donation misuse
# ---------------------------------------------------------------------------

def test_dn_positives():
    found = run_fixture("dn_positive.py", "DN")
    dn601 = [f for f in found if f.rule == "DN601"]
    dn602 = [f for f in found if f.rule == "DN602"]
    assert len(dn601) == 4, found
    assert len(dn602) == 2, found
    msgs = " ".join(f.message for f in dn601)
    assert "self._fwd" in msgs          # the paged.py handle shape
    assert "donate" in msgs
    msgs2 = " ".join(f.message for f in dn602)
    assert "host mirror" in msgs2 and "alias" in msgs2


def test_dn_negatives():
    assert run_fixture("dn_negative.py", "DN") == []


def test_dn_suppressed():
    assert run_fixture("dn_suppressed.py", "DN") == []


def test_dn_real_paged_tree_donates_and_stays_clean():
    """ISSUE 7's first LIVE exercise of the DN guard rails: the paged
    slot server's decode/verify jits (target and draft) now really
    donate their pool args — the exact surface DN601/DN602 were built
    ahead of (PR 6) — and the real tree analyzes clean under both
    rules. The donate_idx pin keeps the rules honest: if the handles
    ever stop parsing, this fails instead of going silently vacuous."""
    import ast
    path = os.path.join(REPO, "tpushare", "models", "paged.py")
    with open(path) as fh:
        tree = ast.parse(fh.read())
    handles = {}
    for node in ast.walk(tree):
        if (isinstance(node, ast.ClassDef)
                and node.name == "PagedSlotServer"):
            handles = dataflow.class_jit_handles(node)
    donating = {n for n, i in handles.items() if i.donates}
    assert donating == {"_decode", "_fused", "_verify",
                        "_draft_decode", "_draft_fused"}, handles
    assert all(handles[n].donate_idx == frozenset({2, 3})
               for n in donating)
    assert analyze_file(path, CONFIG, rules=rules_of("DN"),
                        respect_scope=False) == []


def test_dn602_catches_the_old_spec_loop_alias_shape(tmp_path):
    """The pre-donation _spec_step held the draft pools in LOCALS
    (dpk, dpv = self._dpk, self._dpv) and rebound the attributes only
    after the proposal loop — with donation live, the first dispatch
    kills the buffers the attributes still name. The shipped loop
    rebinds self._dpk/_dpv each step; this pins that the old alias
    shape is a DN602 so it can never come back."""
    found = run_source(tmp_path, """
        import jax

        class FakeSlotServer:
            def __init__(self, core):
                self._draft_decode = jax.jit(core,
                                             donate_argnums=(2, 3))

            def _spec_step(self, params, tok, table, active):
                dpk, dpv = self._dpk, self._dpv
                for j in range(3):
                    dl, dpk, dpv = self._draft_decode(
                        params, tok, dpk, dpv, table, active)
                self._dpk, self._dpv = dpk, dpv
                return dl
        """, rules_of("DN602"))
    assert any(f.rule == "DN602" and "alias" in f.message
               for f in found), found


def test_dn601_red_handle_built_in_init_not_absorbed(tmp_path):
    """Red test: the donation fact lives on a jit handle built in
    __init__ (models/paged.py:813 shape) and the read happens in
    step() — pure value flow, invisible to every syntactic rule."""
    source = """
        import jax

        class MiniPagedSlotServer:
            def __init__(self, fwd):
                self._decode = jax.jit(fwd, donate_argnums=(1,))

            def step(self, params, cache, tok):
                logits, new_cache = self._decode(params, cache, tok)
                self.last_len = cache["lengths"]    # read-after-donate
                return logits, new_cache
        """
    found = run_source(tmp_path, source, rules_of("DN601"))
    assert len(found) == 1 and found[0].rule == "DN601"
    assert "self._decode" in found[0].message
    assert run_source(tmp_path, source, rules_except("DN601"),
                      name="off.py") == []
    entries = baseline_mod.load(CONFIG.resolve(CONFIG.baseline))
    new, _ = baseline_mod.diff(found, entries)
    assert len(new) == 1


def test_dn602_red_np_mirror_not_absorbed(tmp_path):
    found = run_source(tmp_path, """
        import jax
        import numpy as np

        class M:
            def __init__(self, fwd):
                self._fwd = jax.jit(fwd, donate_argnums=(0,))
                self.lengths_np = np.zeros((4,))

            def grow(self, tok):
                return self._fwd(self.lengths_np, tok)
        """, rules_of("DN602"))
    assert len(found) == 1 and found[0].rule == "DN602"
    assert "host mirror" in found[0].message
    entries = baseline_mod.load(CONFIG.resolve(CONFIG.baseline))
    new, _ = baseline_mod.diff(found, entries)
    assert len(new) == 1


# ---------------------------------------------------------------------------
# TE701 — tracer escape
# ---------------------------------------------------------------------------

def test_te_positives():
    found = run_fixture("te_positive.py", "TE")
    assert len(found) == 5, found
    msgs = " ".join(f.message for f in found)
    assert "on self" in msgs
    assert "global" in msgs
    assert "captured mutable" in msgs
    assert ".append()" in msgs


def test_te_negatives():
    assert run_fixture("te_negative.py", "TE") == []


def test_te_suppressed():
    assert run_fixture("te_suppressed.py", "TE") == []


def test_te701_red_wrapped_by_name_not_absorbed(tmp_path):
    """Red test: the store sits in a function jitted BY NAME later
    (f2 = jax.jit(f)) — the jit root resolution, not the decorator,
    must carry the scope."""
    source = """
        import jax

        class Probe:
            def build(self):
                def kernel(x):
                    y = x * 2
                    self.peak = y          # tracer escapes via closure
                    return y
                return jax.jit(kernel)
        """
    found = run_source(tmp_path, source, rules_of("TE701"))
    assert len(found) == 1 and found[0].rule == "TE701"
    assert run_source(tmp_path, source, rules_except("TE701"),
                      name="off.py") == []
    entries = baseline_mod.load(CONFIG.resolve(CONFIG.baseline))
    new, _ = baseline_mod.diff(found, entries)
    assert len(new) == 1


# ---------------------------------------------------------------------------
# JC801 — recompile churn
# ---------------------------------------------------------------------------

def test_jc_positives():
    found = run_fixture("jc_positive.py", "JC")
    assert len(found) == 5, found
    msgs = " ".join(f.message for f in found)
    assert "every tick" in msgs
    assert "per iteration" in msgs
    assert "unhashable list" in msgs
    assert "lambda" in msgs
    assert "fresh closure per call" in msgs


def test_jc_negatives():
    assert run_fixture("jc_negative.py", "JC") == []


def test_jc_suppressed():
    assert run_fixture("jc_suppressed.py", "JC") == []


def test_jc801_red_jit_in_spec_step_not_absorbed(tmp_path):
    source = """
        import jax

        class ChurnSlotServer:
            def _spec_step(self, x):
                verify = jax.jit(lambda v: v + 1)   # rebuilt per round
                return verify(x)
        """
    found = run_source(tmp_path, source, rules_of("JC801"))
    assert len(found) == 1 and found[0].rule == "JC801"
    assert "_spec_step" in found[0].message
    assert run_source(tmp_path, source, rules_except("JC801"),
                      name="off.py") == []
    entries = baseline_mod.load(CONFIG.resolve(CONFIG.baseline))
    new, _ = baseline_mod.diff(found, entries)
    assert len(new) == 1


def test_jc801_lora_hook_shape_is_caught_and_fixed_shape_clean(tmp_path):
    """The genuine triage fix of this PR: an UNMEMOIZED lora_hook-
    shaped factory is a finding; the shipped lru_cache'd shape is
    clean — and the real lora.py must scan clean."""
    bad = """
        def lora_hook(scale=1.0, inner=None):
            def hook(xs):
                return xs
            return hook
        """
    good = """
        import functools

        @functools.lru_cache(maxsize=None)
        def lora_hook(scale=1.0, inner=None):
            def hook(xs):
                return xs
            return hook
        """
    assert len(run_source(tmp_path, bad, rules_of("JC801"))) == 1
    assert run_source(tmp_path, good, rules_of("JC801"),
                      name="good.py") == []
    real = analyze_file(os.path.join(REPO, "tpushare", "models",
                                     "lora.py"),
                        CONFIG, rules=rules_of("JC801"))
    assert real == [], [f.render() for f in real]


# ---------------------------------------------------------------------------
# Dataflow engine units
# ---------------------------------------------------------------------------

def test_env_alias_resolution_and_cell_kill():
    env = dataflow.Env()
    env.bind("a", dataflow.Value("key", "fresh", 1))
    env.bind("b", dataflow.Value("alias", data=("a",)))
    root, v = env.resolve("b")
    assert root == "a" and v.state == "fresh"
    env.bind("ks[0]", dataflow.Value("key", "fresh", 2))
    env.bind("ks", dataflow.Value("keys", "fresh", 3))   # rebind base
    assert env.get("ks[0]") is None                      # cells dropped


def test_resolvable_declines_global_and_nonlocal():
    import ast
    ok = ast.parse("def f(rng):\n    return rng\n").body[0]
    bad = ast.parse("def f():\n    global g\n    g = 1\n").body[0]
    nested = ast.parse(
        "def f():\n    x = 1\n    def g():\n        nonlocal x\n"
        "        x = 2\n    return g\n").body[0]
    assert dataflow.resolvable(ok)
    assert not dataflow.resolvable(bad)
    assert not dataflow.resolvable(nested)


def test_parse_jit_call_shapes():
    import ast
    call = ast.parse(
        "jax.jit(f, donate_argnums=(0, 2), static_argnames=('cfg',))"
    ).body[0].value
    info = dataflow.parse_jit_call(call)
    assert info.donate_idx == frozenset({0, 2})
    assert info.static_names == frozenset({"cfg"})
    assert info.target == "f"
    part = ast.parse(
        "functools.partial(jax.jit, static_argnames=('n',))"
    ).body[0].value
    info2 = dataflow.parse_jit_call(part)
    assert info2.static_names == frozenset({"n"})
    assert dataflow.parse_jit_call(
        ast.parse("np.zeros((4,))").body[0].value) is None


def test_class_jit_handles_finds_init_assignments():
    import ast
    tree = ast.parse(textwrap.dedent("""
        import jax
        class S:
            def __init__(self, fwd):
                self._decode = jax.jit(fwd, donate_argnums=(1,))
                self.plain = jax.jit(fwd)
        """))
    cls = tree.body[1]
    handles = dataflow.class_jit_handles(cls)
    assert handles["_decode"].donate_idx == frozenset({1})
    assert not handles["plain"].donates


def test_param_key_consume_fixpoint(tmp_path):
    src = tmp_path / "m.py"
    src.write_text(textwrap.dedent("""
        import jax

        def leaf(key):
            return jax.random.normal(key, (2,))

        def mid(k):
            return leaf(k)

        def folder(key):
            return jax.random.fold_in(key, 3)
        """))
    index = callgraph.build_index([str(src)])
    path = str(src)
    assert index.func(f"{path}::leaf").param_key_consume == {"key"}
    assert index.func(f"{path}::mid").param_key_consume == {"k"}
    assert index.func(f"{path}::folder").param_key_consume == set()


def test_returns_closure_summary(tmp_path):
    src = tmp_path / "m.py"
    src.write_text(textwrap.dedent("""
        def factory(scale):
            def hook(x):
                return x * scale
            return hook

        def plain(x):
            return x
        """))
    index = callgraph.build_index([str(src)])
    assert index.func(f"{src}::factory").returns_closure
    assert not index.func(f"{src}::plain").returns_closure


def test_early_return_does_not_poison_fallthrough(tmp_path):
    """Termination-aware joins: a branch that returns contributes
    nothing to the post-if environment."""
    found = run_source(tmp_path, """
        import jax

        def pick(rng, greedy):
            if greedy:
                return jax.random.normal(rng, (2,))
            return jax.random.uniform(rng, (2,))
        """, rules_of("PK"))
    assert found == []


def test_loop_break_rebind_shapes(tmp_path):
    found = run_source(tmp_path, """
        import jax

        def gen(rng, n):
            out = []
            while True:
                rng, k = jax.random.split(rng)
                out.append(jax.random.normal(k, (2,)))
                if len(out) >= n:
                    break
            return out
        """, rules_of("PK"))
    assert found == []


# ---------------------------------------------------------------------------
# Parallel fact extraction (--jobs)
# ---------------------------------------------------------------------------

def test_jobs_results_byte_identical_to_serial():
    """The satellite contract: --jobs N only prefills the same facts
    cache the serial path reads, so findings render identically."""
    paths = [CONFIG.resolve(p) for p in CONFIG.paths]
    callgraph.clear_cache()
    serial = [f.render() for f in analyze_paths(paths, CONFIG)]
    callgraph.clear_cache()
    parallel = [f.render() for f in analyze_paths(paths, CONFIG,
                                                  jobs=4)]
    assert serial == parallel


def test_prefetch_skips_warm_cache(tmp_path):
    src = tmp_path / "m.py"
    src.write_text("def f():\n    pass\n")
    first = callgraph.module_facts(str(src), None)
    callgraph.prefetch_facts([str(src)], jobs=4)     # warm: no-op
    assert callgraph.module_facts(str(src), None) is first


# ---------------------------------------------------------------------------
# Real-tree pins: the new families gate the actual tree
# ---------------------------------------------------------------------------

def test_real_tree_clean_under_new_families():
    """PK/DN/TE/JC over the shipping models tree: zero unbaselined
    findings (triage landed the lora_hook fix; donation rules have no
    real surface until the mesh ServeEngine). This is the alarm wire:
    a new reuse/donation/escape/churn anywhere in the policed trees
    is a NEW finding, not churn."""
    targets = [os.path.join(REPO, "tpushare", "models"),
               os.path.join(REPO, "tpushare", "ops"),
               os.path.join(REPO, "tpushare", "parallel")]
    findings = analyze_paths(targets, CONFIG,
                             rules=[r for r in all_rules()
                                    if r.id[:2] in ("PK", "DN", "TE",
                                                    "JC")])
    entries = baseline_mod.load(CONFIG.resolve(CONFIG.baseline))
    new, _ = baseline_mod.diff(findings, entries)
    assert new == [], [f.render() for f in new]


def test_seeded_key_reuse_fails_the_gate(tmp_path):
    """End-to-end red: a seeded PK501 in a swept location produces a
    NEW finding the baseline does not absorb (the whole-tree gate
    covers the new families)."""
    bad = tmp_path / "sneaky.py"
    bad.write_text(textwrap.dedent("""
        import jax

        def f(rng):
            a = jax.random.normal(rng, (2,))
            return a + jax.random.uniform(rng, (2,))
        """))
    findings = analyze_file(str(bad), CONFIG, rules=rules_of("PK"),
                            respect_scope=False)
    entries = baseline_mod.load(CONFIG.resolve(CONFIG.baseline))
    new, _ = baseline_mod.diff(findings, entries)
    assert {f.rule for f in new} == {"PK501"}


# ---------------------------------------------------------------------------
# Review-hardening regressions: three false-positive shapes caught in
# code review, each reproduced live before the fix
# ---------------------------------------------------------------------------

def test_alias_severed_when_root_rebound(tmp_path):
    """`k0 = rng; rng = fold_in(rng, 1)` — k0 keeps denoting the
    ORIGINAL key after the root is rebound; drawing each once is
    clean (rebind severs aliases by materializing the old value)."""
    found = run_source(tmp_path, """
        import jax

        def f(rng):
            k0 = rng
            rng = jax.random.fold_in(rng, 1)
            a = jax.random.normal(rng, (2,))
            return a + jax.random.normal(k0, (2,))
        """, rules_of("PK"))
    assert found == [], found
    # ...while a live alias still propagates consumption (the severing
    # must not weaken the alias_reuse positive)
    still = run_source(tmp_path, """
        import jax

        def f(rng):
            k = rng
            a = jax.random.normal(rng, (2,))
            return a + jax.random.uniform(k, (2,))
        """, rules_of("PK501"), name="live.py")
    assert len(still) == 1


def test_return_in_loop_does_not_self_flag(tmp_path):
    """A frame-terminating loop body (return/raise on every path)
    runs no second pass and the zero-iteration fall-through continues
    from the PRE-loop env — the draw must not flag itself."""
    found = run_source(tmp_path, """
        import jax

        def f(rng, xs):
            for x in xs:
                return jax.random.normal(rng, (2,))
            return jax.random.uniform(rng, (2,))
        """, rules_of("PK"))
    assert found == [], found
    # unconditional break: body runs at most once, no second pass
    found2 = run_source(tmp_path, """
        import jax

        def f(rng, xs):
            for x in xs:
                a = jax.random.normal(rng, (2,))
                break
            return 0
        """, rules_of("PK"), name="brk.py")
    assert found2 == [], found2
    # loop-carried reuse still flags (two-pass analysis intact)
    still = run_source(tmp_path, """
        import jax

        def f(rng, xs):
            out = []
            for x in xs:
                out.append(jax.random.normal(rng, (2,)))
            return out
        """, rules_of("PK501"), name="carry.py")
    assert len(still) == 1


def test_except_fallback_draw_not_double_counted(tmp_path):
    """Handlers run after ANY prefix of the body (possibly none), so
    the idiomatic fallback — draw in try, draw again in except — is
    one consumption per path, not two."""
    found = run_source(tmp_path, """
        import jax

        def f(rng):
            try:
                return jax.random.normal(rng, (2,))
            except Exception:
                return jax.random.normal(rng, (2,))
        """, rules_of("PK"))
    assert found == [], found
    # reuse AFTER the whole try/except still flags: the post-try env
    # joins body and handler effects
    still = run_source(tmp_path, """
        import jax

        def f(rng):
            try:
                a = jax.random.normal(rng, (2,))
            except Exception:
                a = None
            return jax.random.uniform(rng, (2,))
        """, rules_of("PK501"), name="after.py")
    assert len(still) == 1


def test_multi_candidate_resolution_consumes_once(tmp_path):
    """Duck/attr resolution can yield several candidate callees for
    one site; the one runtime call consumes each arg at most ONCE —
    per-candidate consumption would flag the site against itself."""
    found = run_source(tmp_path, """
        import jax

        class ASrv:
            def draw(self, key):
                return jax.random.normal(key, (2,))

        class BSrv:
            def draw(self, key):
                return jax.random.uniform(key, (2,))

        class Engine:
            def __init__(self, fast):
                if fast:
                    self.x = ASrv()
                else:
                    self.x = BSrv()

            def tick(self, k):
                return self.x.draw(k)       # ONE use, two candidates
        """, rules_of("PK"))
    assert found == [], found


def test_hook_factory_nested_helper_lambda_not_flagged(tmp_path):
    """A hand-memoized factory whose NESTED helper returns a lambda is
    not itself returning a fresh closure — the shared
    callgraph._returns_closure prune applies (divergence regression)."""
    found = run_source(tmp_path, """
        _CACHE = {}

        def cached_hook(cfg):
            def _build():
                return lambda xs: xs
            if cfg not in _CACHE:
                _CACHE[cfg] = _build()
            return _CACHE[cfg]
        """, rules_of("JC801"))
    assert found == [], found
    # the plain fresh-closure factory still flags
    still = run_source(tmp_path, """
        def scale_hook(s):
            def hook(xs):
                return xs
            return hook
        """, rules_of("JC801"), name="fresh.py")
    assert len(still) == 1


def test_finally_runs_even_when_all_paths_terminated(tmp_path):
    """`finally` executes on every path — a consume inside it after a
    try-return must still be analyzed (and flag reuse)."""
    found = run_source(tmp_path, """
        import jax

        def f(rng):
            a = jax.random.normal(rng, (2,))
            try:
                return a
            finally:
                jax.random.uniform(rng, (2,))   # reuse, in finally
        """, rules_of("PK501"))
    assert len(found) == 1, found


def test_te701_tuple_unpack_to_self(tmp_path):
    found = run_source(tmp_path, """
        import jax

        class M:
            @jax.jit
            def stats(self, x):
                self.mean, self.var = x.mean(), x.var()
                return x
        """, rules_of("TE701"))
    assert len(found) == 2, found
    assert all("on self" in f.message for f in found)


def test_te701_vararg_kwarg_params_are_locals(tmp_path):
    found = run_source(tmp_path, """
        import jax

        @jax.jit
        def f(x, *scratch, **aux):
            # parameters are trace-local whatever their spelling
            out = [s + x for s in scratch]
            return out, dict(aux)
        """, rules_of("TE701"))
    assert found == [], found


def test_dn601_method_call_on_donated_buffer(tmp_path):
    """`buf.block_until_ready()` after donating buf IS a read — the
    attribute-chain root must reach the domain's on_load."""
    found = run_source(tmp_path, """
        import jax

        STEP = jax.jit(lambda a, b: a + b, donate_argnums=(0,))

        def f(buf, x):
            out = STEP(buf, x)
            buf.block_until_ready()
            return out
        """, rules_of("DN601"))
    assert len(found) == 1, found
    assert "'buf'" in found[0].message


def test_jc801_loop_inside_tick_reports_once(tmp_path):
    """One construction site hit by BOTH rebuild passes (loop inside a
    tick method) is one defect, one finding — the more specific
    step-loop message wins."""
    found = run_source(tmp_path, """
        import jax

        class FooSlotServer:
            def step(self, xs):
                for x in xs:
                    f = jax.jit(lambda v: v)
                return 0
        """, rules_of("JC801"))
    assert len(found) == 1, found
    assert "FooSlotServer.step" in found[0].message


def test_mixed_break_return_join_keeps_break_arm_state(tmp_path):
    """When one if-arm returns and the sibling breaks, the loop
    continuation is reached ONLY through the break arm — the return
    arm's consumption must not leak past the loop."""
    found = run_source(tmp_path, """
        import jax

        def f(rng, xs):
            for x in xs:
                if x:
                    return jax.random.normal(rng, (2,))
                else:
                    break
            return jax.random.uniform(rng, (2,))
        """, rules_of("PK"))
    assert found == [], found
    # mirrored arm order must behave identically
    found2 = run_source(tmp_path, """
        import jax

        def f(rng, xs):
            for x in xs:
                if x:
                    break
                else:
                    return jax.random.normal(rng, (2,))
            return jax.random.uniform(rng, (2,))
        """, rules_of("PK"), name="mirror.py")
    assert found2 == [], found2


def test_dn601_through_local_alias_of_module_handle(tmp_path):
    found = run_source(tmp_path, """
        import jax

        STEP = jax.jit(lambda a, b: a + b, donate_argnums=(0,))

        def g(buf, x):
            h = STEP
            out = h(buf, x)
            return out + buf
        """, rules_of("DN601"))
    assert len(found) == 1, found
    assert "'buf'" in found[0].message
