"""flcheck static-analysis tests: the lint rules against good/bad fixtures,
the jaxpr taint proofs against the REAL round bodies (and a deliberately
broken mask-after-psum pipeline), and the hot-path guards.

The taint proofs here are the load-bearing privacy regression: they fail if
anyone reorders a transform stage past the aggregation collective on ANY
topology, even when every numeric pin still passes (e.g. masks that cancel
in the sum regardless of where they were applied).
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis import recompile, taint
from repro.analysis.cli import find_repo_root, lint_file, main as cli_main
from repro.analysis.rules import RULES, Suppressions
from repro.analysis.concurrency import check_source as conc_check
from repro.analysis.determinism import check_source as det_check
from repro.analysis.dtypes import check_source as dt_check
from repro.analysis.prng_lint import check_source as prng_check
from repro.configs.base import SecureAggConfig, TransformConfig
from repro.core import transforms as transforms_mod

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "flcheck")
# pretend scope paths: FLC004/FLC005 only fire under core/, FLC006-FLC009
# only under serving/ (see rules.py)
CORE_REL = "src/repro/core/fixture.py"
SERVING_REL = "src/repro/serving/fixture.py"
# which pretend path exercises each rule's scope
FIXTURE_REL = {"FLC006": SERVING_REL, "FLC007": SERVING_REL,
               "FLC008": SERVING_REL, "FLC009": SERVING_REL}

ALL_CHECKS = (prng_check, det_check, dt_check, conc_check)


def _run_all(source: str, rel: str = CORE_REL):
    return [f for check in ALL_CHECKS for f in check(source, rel)
            if RULES[f.code].in_scope(rel)]


def _fixture(name: str) -> str:
    with open(os.path.join(FIXTURES, name), "r", encoding="utf-8") as f:
        return f.read()


# ------------------------------------------------------------- level-2 lint
@pytest.mark.parametrize("code", ["FLC001", "FLC002", "FLC003", "FLC004",
                                  "FLC005", "FLC006", "FLC007", "FLC008",
                                  "FLC009"])
def test_bad_fixture_triggers_exactly_its_rule(code):
    rel = FIXTURE_REL.get(code, CORE_REL)
    findings = _run_all(_fixture(f"bad_{code.lower()}.py"), rel)
    assert findings, f"bad fixture for {code} produced no findings"
    assert {f.code for f in findings} == {code}, (
        f"bad fixture for {code} leaked other codes: "
        f"{[(f.code, f.line, f.message) for f in findings]}")
    assert not any(f.suppressed for f in findings)


def test_good_fixture_is_clean():
    findings = _run_all(_fixture("good_clean.py"))
    assert findings == [], [(f.code, f.line, f.message) for f in findings]


def test_good_serving_fixture_is_clean():
    findings = _run_all(_fixture("good_serving.py"), SERVING_REL)
    assert findings == [], [(f.code, f.line, f.message) for f in findings]


def test_scoped_rules_do_not_fire_outside_scope():
    # the FLC004/FLC005 fixtures are clean when the file lives in launch/,
    # and the serving-concurrency fixtures are clean OUTSIDE serving/
    rel = "src/repro/launch/fixture.py"
    for name in ("bad_flc004.py", "bad_flc005.py", "bad_flc006.py",
                 "bad_flc007.py", "bad_flc008.py", "bad_flc009.py"):
        findings = _run_all(_fixture(name), rel)
        assert findings == [], (name, [(f.code, f.line) for f in findings])


def test_suppression_with_rationale_suppresses():
    src = ("import jax\n"
           "k = jax.random.PRNGKey(0)  "
           "# flcheck: disable=FLC001 (test fixture)\n")
    (f,) = prng_check(src, CORE_REL)
    assert f.suppressed and f.suppress_reason == "test fixture"


def test_suppression_without_rationale_is_fatal():
    src = ("import jax\n"
           "k = jax.random.PRNGKey(0)  # flcheck: disable=FLC001\n")
    (f,) = prng_check(src, CORE_REL)
    assert not f.suppressed            # no rationale -> not suppressed
    assert Suppressions(src).missing_reason == [2]


def test_suppression_on_line_above():
    src = ("import jax\n"
           "# flcheck: disable=FLC001 (covers next line)\n"
           "k = jax.random.PRNGKey(0)\n")
    (f,) = prng_check(src, CORE_REL)
    assert f.suppressed


def test_key_reuse_not_flagged_for_split_rebind():
    src = ("import jax\n"
           "def f(key):\n"
           "    key, sub = jax.random.split(key)\n"
           "    a = jax.random.normal(sub, (2,))\n"
           "    key, sub = jax.random.split(key)\n"
           "    b = jax.random.normal(sub, (2,))\n"
           "    return a + b\n")
    assert prng_check(src, CORE_REL) == []


def test_repo_src_tree_is_flcheck_clean():
    """The shipped source tree has zero unsuppressed findings and every
    suppression carries a rationale — the CI lint gate, as a test."""
    root = find_repo_root(os.path.dirname(__file__))
    src_dir = os.path.join(root, "src")
    bad = []
    for dirpath, _, filenames in os.walk(src_dir):
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            findings, errors = lint_file(os.path.join(dirpath, fn), root)
            bad.extend(errors)
            bad.extend(f.render() for f in findings if not f.suppressed)
    assert bad == [], "\n".join(bad)


def test_cli_exit_codes(tmp_path, capsys):
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    assert cli_main([str(clean)]) == 0
    dirty = tmp_path / "dirty.py"
    dirty.write_text("import jax\nk = jax.random.PRNGKey(7)\n")
    assert cli_main([str(dirty)]) == 1
    out = capsys.readouterr().out
    assert "FLC001" in out


def test_cli_missing_path_is_fatal(tmp_path, capsys):
    """A typo'd lint target must exit 2 with a clear message, never pass
    as 'clean' (the satellite fix: missing != nothing-to-lint)."""
    missing = tmp_path / "no_such_dir"
    assert cli_main([str(missing)]) == 2
    assert "does not exist" in capsys.readouterr().err


def test_cli_empty_dir_is_fatal(tmp_path, capsys):
    empty = tmp_path / "empty"
    empty.mkdir()
    assert cli_main([str(empty)]) == 2
    assert "no Python files" in capsys.readouterr().err


def test_cli_non_python_file_is_fatal(tmp_path, capsys):
    txt = tmp_path / "notes.txt"
    txt.write_text("hello\n")
    assert cli_main([str(txt)]) == 2
    assert "not a Python file" in capsys.readouterr().err


def test_cli_list_rules_covers_catalog(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for code in RULES:
        assert code in out


# --------------------------------------------------------- level-1: taint
# int8: the ring quantizer under masking reserves cohort-size rounding
# headroom, and the 8-device flat/hier traces dispatch a cohort of 8 —
# too big for an int4 ring (2^3 - 1 - 8 < 1), fine in int8 (119 levels)
FULL_T = TransformConfig(clip_norm=1.0, noise_multiplier=0.5,
                         quantize_bits=8)
SECURE = SecureAggConfig(enabled=True)


def test_taint_proves_vmap_full_stack():
    rep = taint.verify_pipeline("vmap", FULL_T, SECURE)
    assert rep.proved, rep.render()
    assert rep.required == frozenset({"clip", "noise", "quantize", "mask"})
    assert rep.checked > 0 and rep.sources > 0    # non-vacuous


def test_taint_proves_semi_sync_dispatch_path():
    rep = taint.verify_pipeline("semi_sync", FULL_T, SECURE)
    assert rep.proved, rep.render()


def test_taint_proves_clip_only_config():
    rep = taint.verify_pipeline("vmap", TransformConfig(clip_norm=1.0))
    assert rep.proved, rep.render()
    assert rep.required == frozenset({"clip"})


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices (run via ./test.sh)")
def test_taint_proves_flat_psum_topology():
    rep = taint.verify_pipeline("flat", FULL_T, SECURE)
    assert rep.proved, rep.render()


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices (run via ./test.sh)")
def test_taint_proves_hierarchical_topology():
    rep = taint.verify_pipeline("hier", FULL_T, SECURE)
    assert rep.proved, rep.render()
    # hierarchical = two chained psums; both crossings were checked
    assert rep.checked >= 2


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices (run via ./test.sh)")
def test_taint_rejects_mask_after_psum():
    """The regression the whole pass exists for: a pipeline that aggregates
    FIRST and sanitizes after must be rejected — numerically the masks
    would still cancel in the sum, so no loss pin can catch this."""
    mesh = jax.make_mesh((8,), ("clients",))
    stack = transforms_mod.make_stack(TransformConfig(clip_norm=1.0), None)

    def broken(deltas, keys):
        deltas = taint.tag_private(deltas)
        summed = jax.tree.map(lambda d: jax.lax.psum(d, "clients"), deltas)
        return jax.vmap(stack)(summed, keys)

    fn = jax.shard_map(broken, mesh=mesh,
                       in_specs=(P("clients"), P("clients")),
                       out_specs=P("clients"), check_vma=False)
    with taint.analysis_mode():
        jx = jax.make_jaxpr(fn)(jnp.zeros((8, 3)),
                                jnp.zeros((8, 2), jnp.uint32))
    rep = taint.analyze_closed(jx, frozenset({"clip"}))
    assert not rep.ok
    assert any(v.primitive == "psum" and "clip" in v.missing
               for v in rep.violations), rep.render()


def test_taint_rejects_missing_stage_label():
    """A pipeline that clips but skips noising fails a clip+noise policy."""
    stack = transforms_mod.make_stack(TransformConfig(clip_norm=1.0), None)

    def partial_pipeline(deltas, keys):
        deltas = taint.tag_private(deltas)
        deltas = jax.vmap(stack)(deltas, keys)       # clip only
        return taint.boundary(jnp.sum(deltas, axis=0))

    with taint.analysis_mode():
        jx = jax.make_jaxpr(partial_pipeline)(
            jnp.zeros((4, 3)), jnp.zeros((4, 2), jnp.uint32))
    rep = taint.analyze_closed(jx, frozenset({"clip", "noise"}))
    assert not rep.ok
    assert all(v.missing == frozenset({"noise"}) for v in rep.violations)


def test_taint_label_meet_on_mixing():
    """Mixing a sanitized value with an unsanitized one weakens the labels
    to the intersection — the mixed value must NOT count as sanitized."""
    def mix(x):
        priv = taint.tag_private(x)
        cleaned = taint.declassify(priv * 2.0, "clip")
        mixed = cleaned + priv                       # re-contaminated
        return taint.boundary(jnp.sum(mixed))

    with taint.analysis_mode():
        jx = jax.make_jaxpr(mix)(jnp.zeros((3,)))
    rep = taint.analyze_closed(jx, frozenset({"clip"}))
    assert not rep.ok and rep.violations[0].missing == frozenset({"clip"})


def test_taint_markers_are_production_noops():
    """Outside analysis_mode the markers add NOTHING to the jaxpr and the
    traced math is unchanged."""
    def f(x):
        x = taint.tag_private(x)
        x = taint.declassify(x, "clip")
        return taint.boundary(x) * 2.0

    jx = jax.make_jaxpr(f)(jnp.ones((2,)))
    prims = {e.primitive.name for e in jx.jaxpr.eqns}
    assert not any(p.startswith("flcheck_") for p in prims), prims
    assert float(jax.jit(f)(jnp.ones(()))) == 2.0


def test_taint_scan_fixpoint_catches_loop_carried_taint():
    """Taint flowing through a scan carry (accumulated over iterations)
    still reaches the boundary check — the interpreter iterates the body
    to a fixpoint instead of analyzing it once."""
    def f(x):
        priv = taint.tag_private(x)

        def step(carry, _):
            return carry + priv, None                # taint enters carry

        acc, _ = jax.lax.scan(step, jnp.zeros_like(x), None, length=3)
        return taint.boundary(jnp.sum(acc))

    with taint.analysis_mode():
        jx = jax.make_jaxpr(f)(jnp.zeros((3,)))
    rep = taint.analyze_closed(jx, frozenset({"clip"}))
    assert not rep.ok, "loop-carried taint escaped the scan fixpoint"


def test_untagged_loss_release_is_not_flagged():
    """The weighted scalar loss release (the accepted disclosure in
    docs/privacy.md) carries no taint, so an empty-required policy on the
    identity config stays clean AND non-vacuous for the model tree."""
    rep = taint.verify_pipeline("vmap", TransformConfig())
    assert rep.proved, rep.render()
    assert rep.required == frozenset()


# ----------------------------------------------------- hot-path guards
@pytest.mark.slow
def test_round_hot_path_no_recompiles_no_transfers():
    report, transfer_err = recompile.check_round_hot_path()
    assert report.ok, report.render()
    assert transfer_err is None, transfer_err


def test_recompile_guard_catches_static_arg_abuse():
    """A per-step value threaded through a STATIC argnum (instead of being
    traced) retraces every step — exactly what the guard must flag."""
    @partial(jax.jit, static_argnums=(1,))
    def poisoned(x, n):
        return x * n

    def step(i):
        return poisoned(jnp.ones(()), i)   # i static -> new trace each step

    rep = recompile.count_recompiles(step, steps=2,
                                     cache_size=poisoned._cache_size)
    assert not rep.ok and rep.new_entries_per_step == [1, 1]
