"""Tests for the static plan analyzer (``repro.analysis``) and the unified
lint framework.

Structure mirrors the analyzer's contract:

* every oracle-suite program analyzes **clean** (``report.ok``);
* every pass has a deliberately broken fixture it **catches** — a mutated
  plan, a bad donation, an unstable capture, a skewed cost model — so the
  checks are known to be falsifiable, not vacuously green;
* the comm-cost pass is pinned exactly against the napkin
  ``cross_pod_bytes`` model and ``models/tpcomm`` wire math (satellite:
  the three int8 wire models must agree to the byte);
* the lint registry reproduces the historical compat grep and donation
  lint (zero violations on this tree) and each rule fires on a synthetic
  violating tree.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis
from repro import core as drjax
from repro.analysis import commcost
from repro.analysis.lints import run_lints
from repro.compression import PACK_COLS, int8_roundtrip
from repro.core import interpreter as interp
from repro.models import tpcomm
from repro.runtime import executor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# program zoo (the oracle-suite shapes the analyzer must pass clean)
# ---------------------------------------------------------------------------


def flat_plan(n=8, d=None):
    @drjax.program(partition_size=n)
    def f(x, xs):
        y = drjax.broadcast(x)
        z = drjax.map_fn(lambda a, b: a * b, (y, xs))
        return drjax.reduce_mean(z)

    shape = (n,) if d is None else (n, d)
    args = (jnp.float32(1.0), jnp.zeros(shape, jnp.float32))
    return drjax.build_plan(jax.make_jaxpr(f)(*args), n), args


def nested_plan(P=2, m=4):
    @drjax.program(placements={"pods": P, "clients": m})
    def f(x, data):
        y = drjax.broadcast(x)
        z = drjax.map_fn(lambda a, b: a * b, (y, data))
        partial = drjax.reduce_mean(z, placement="clients")
        return drjax.reduce_mean(partial, placement="pods")

    args = (jnp.float32(2.0), jnp.zeros((P, m), jnp.float32))
    jx = jax.make_jaxpr(f)(*args)
    return drjax.build_plan(jx, {"pods": P, "clients": m}), args


def scan_round_plan(n=4, length=3):
    @drjax.program(partition_size=n)
    def f(m, ys):
        def body(m, _):
            g = drjax.reduce_mean(
                drjax.map_fn(lambda a, b: a - b, (drjax.broadcast(m), ys)))
            return m - 0.5 * g, g

        m, gs = jax.lax.scan(body, m, None, length=length)
        return m, gs

    args = (jnp.float32(0.3), jnp.arange(float(n)))
    return drjax.build_plan(jax.make_jaxpr(f)(*args), n), args


def while_pred_comm_plan(n=4):
    """Data-dependent while whose PREDICATE reduces (adversarial nesting)."""

    @drjax.program(partition_size=n)
    def f(x, xs):
        def cond(c):
            s = drjax.reduce_mean(
                drjax.map_fn(lambda a, b: a + b, (drjax.broadcast(c), xs)))
            return s < 10.0

        return jax.lax.while_loop(cond, lambda c: c + 1.0, x)

    args = (jnp.float32(0.0), jnp.arange(float(n)))
    return drjax.build_plan(jax.make_jaxpr(f)(*args), n), args


def cond_comm_plan(n=4):
    @drjax.program(partition_size=n)
    def f(p, x, xs):
        def talk(x):
            y = drjax.broadcast(x)
            return drjax.reduce_mean(
                drjax.map_fn(lambda a, b: a * b, (y, xs)))

        return jax.lax.cond(p, talk, lambda x: x * 2.0, x)

    args = (jnp.array(True), jnp.float32(1.0), jnp.arange(float(n)))
    return drjax.build_plan(jax.make_jaxpr(f)(*args), n), args


def scan_of_cond_plan(n=4, length=5):
    """Comm inside a CondStage branch inside a LoopStage (adversarial)."""

    @drjax.program(partition_size=n)
    def f(m, ys):
        def body(m, i):
            def talk(m):
                return drjax.reduce_mean(
                    drjax.map_fn(
                        lambda a, b: a + b, (drjax.broadcast(m), ys)))

            m = jax.lax.cond(i % 2 == 0, talk, lambda m: m, m)
            return m, ()

        m, _ = jax.lax.scan(body, m, jnp.arange(length))
        return m

    args = (jnp.float32(0.0), jnp.arange(float(n)))
    return drjax.build_plan(jax.make_jaxpr(f)(*args), n), args


def fused_hier_plan(n=8, P=2, d=512):
    @drjax.program(partition_size=n)
    def f(xs):
        return drjax.hierarchical_reduce_mean(
            xs, num_supergroups=P, compress_fn=int8_roundtrip)

    args = (jnp.zeros((n, d), jnp.float32),)
    return drjax.build_plan(jax.make_jaxpr(f)(*args), n), args


ORACLE_PROGRAMS = {
    "flat": flat_plan,
    "nested": nested_plan,
    "scan_round": scan_round_plan,
    "while_pred_comm": while_pred_comm_plan,
    "cond_comm": cond_comm_plan,
    "scan_of_cond": scan_of_cond_plan,
    "fused_hier": fused_hier_plan,
}


# ---------------------------------------------------------------------------
# oracle suite: every program analyzes clean
# ---------------------------------------------------------------------------


class TestOracleSuiteClean:
    @pytest.mark.parametrize("name", sorted(ORACLE_PROGRAMS))
    def test_analyze_ok(self, name):
        plan, _ = ORACLE_PROGRAMS[name]()
        report = plan.analyze()
        assert report.ok, f"{name}: {report}"
        report.raise_if_errors()  # must be a no-op when ok

    def test_fused_hier_regroup_is_info_not_error(self):
        plan, _ = fused_hier_plan()
        report = plan.analyze()
        infos = report.by_code("placement/regroup-boundary")
        assert infos and all(f.severity == "info" for f in infos)

    def test_subplans_iterates_nested(self):
        plan, _ = scan_of_cond_plan()
        plans = plan.subplans()
        assert plans[0] is plan and len(plans) >= 3  # top + body + branches


# ---------------------------------------------------------------------------
# placement safety: broken fixtures
# ---------------------------------------------------------------------------


class TestPlacementSafety:
    def _comm_in_local_mutant(self):
        plan, _ = cond_comm_plan()
        cond_stage = next(
            s for s in plan.stages if isinstance(s, interp.CondStage))
        bp = next(
            b for b in cond_stage.branch_plans
            if any(isinstance(s, interp.Reduce) for s in b.stages))
        ri = next(
            i for i, s in enumerate(bp.stages)
            if isinstance(s, interp.Reduce))
        bp.stages[ri] = interp.LocalCompute(
            at_groups=True, eqns=[bp.stages[ri].eqn])
        return plan

    def test_comm_inside_local_via_cond_branch(self):
        """A reduce smuggled into a GROUP_COMPUTE stage inside a cond branch
        is caught at depth, with the nested stage named."""
        plan = self._comm_in_local_mutant()
        findings = analysis.check_placement_safety(plan)
        errs = [f for f in findings if f.code == "placement/comm-in-local"]
        assert len(errs) == 1
        assert errs[0].stage and "_b" in errs[0].stage  # nested branch name
        with pytest.raises(Exception):
            plan.check_locality()  # the legacy checker agrees

    def test_comm_in_local_fails_analyze_and_raises(self):
        plan = self._comm_in_local_mutant()
        report = plan.analyze(comm_cost=False)
        assert not report.ok
        with pytest.raises(AssertionError, match="comm-in-local"):
            report.raise_if_errors()

    def test_broken_pairing_detected(self):
        plan, _ = nested_plan()
        bstage = next(
            s for s in plan.stages if isinstance(s, interp.Broadcast))
        bstage.source = "clients"  # outermost broadcast must source "server"
        findings = analysis.check_placement_safety(plan)
        assert any(f.code == "placement/pairing" for f in findings)

    def test_clean_plans_have_no_placement_findings(self):
        for maker in (flat_plan, nested_plan, scan_round_plan):
            plan, _ = maker()
            assert analysis.check_placement_safety(plan) == []


# ---------------------------------------------------------------------------
# donation / aliasing
# ---------------------------------------------------------------------------


class TestDonation:
    def test_round_style_donation_clean(self):
        @drjax.program(partition_size=4)
        def f(params, xs):
            y = drjax.broadcast(params)
            z = drjax.map_fn(lambda a, b: a + b, (y, xs))
            return params + drjax.reduce_mean(z)

        args = (jnp.arange(3.0), jnp.zeros((4, 3), jnp.float32))
        plan = drjax.build_plan(jax.make_jaxpr(f)(*args), 4)
        assert plan.analyze(donate_argnums=(0,)).ok

    def test_use_after_donate_fixture(self):
        """Donating x whose alias target is produced BEFORE x's last read
        must be an error: the late read observes an overwritten buffer."""

        @drjax.program(partition_size=4)
        def f(x, ys):
            a = x + 1.0
            s = drjax.reduce_mean(ys)
            return a, x * s

        args = (jnp.arange(3.0), jnp.arange(4.0))
        plan = drjax.build_plan(jax.make_jaxpr(f)(*args), 4)
        report = plan.analyze(donate_argnums=(0,))
        assert not report.ok
        errs = report.by_code("donation/use-after-donate")
        assert len(errs) == 1 and "stage_2" in errs[0].message
        # without the donation the same plan is clean
        assert plan.analyze().ok

    def test_dropped_donation_explains_why(self):
        @drjax.program(partition_size=4)
        def f(big, xs):
            s = drjax.reduce_mean(xs)
            return s + big.sum()  # big is read, but no (3,)-shaped output

        args = (jnp.arange(3.0), jnp.arange(4.0))
        plan = drjax.build_plan(jax.make_jaxpr(f)(*args), 4)
        report = plan.analyze(donate_argnums=(0,))
        assert report.ok  # dropped donation is a warning, not an error
        warns = report.by_code("donation/dropped")
        assert len(warns) == 1

    def test_carry_not_eligible_when_init_escapes(self):
        """A loop carry whose init is also a plan OUTPUT cannot be donated
        into the loop in place."""

        @drjax.program(partition_size=4)
        def f(m, ys):
            def body(m, _):
                g = drjax.reduce_mean(
                    drjax.map_fn(
                        lambda a, b: a - b, (drjax.broadcast(m), ys)))
                return m - g, ()

            out, _ = jax.lax.scan(body, m, None, length=2)
            return out, m  # m escapes alongside the loop result

        args = (jnp.float32(0.3), jnp.arange(4.0))
        plan = drjax.build_plan(jax.make_jaxpr(f)(*args), 4)
        findings = analysis.analyze_donation(plan)
        assert any(f.code == "donation/carry-not-eligible" for f in findings)

    def test_compiled_plan_donation_report(self):
        plan, args = scan_round_plan()
        compiled = plan.compile(donate_argnums=(0,))
        report = compiled.donation_report()
        assert report.ok

    def test_bad_argnum_is_error(self):
        plan, _ = flat_plan()
        report = plan.analyze(donate_argnums=(17,))
        assert report.by_code("donation/bad-argnum")


# ---------------------------------------------------------------------------
# retrace hazards + fingerprint explanation
# ---------------------------------------------------------------------------


def _captured_scalar_plan(value):
    c = jnp.array([value], jnp.float32)  # closed over -> captured const

    @drjax.program(partition_size=4)
    def f(xs):
        z = drjax.map_fn(lambda a: a * c[0], xs)
        return drjax.reduce_mean(z)

    return drjax.build_plan(jax.make_jaxpr(f)(jnp.arange(4.0)), 4)


class TestRetrace:
    def test_unstable_const_flagged(self):
        plan = _captured_scalar_plan(0.1)
        findings = analysis.analyze_retrace(plan)
        warns = [f for f in findings if f.code == "retrace/unstable-const"]
        assert len(warns) == 1
        assert "plan input" in warns[0].message  # tells the user the fix

    def test_explain_fingerprint_mismatch_pinpoints_const(self):
        pa = _captured_scalar_plan(0.1)
        pb = _captured_scalar_plan(0.2)
        assert executor.plan_fingerprint(pa) != executor.plan_fingerprint(pb)
        diffs = analysis.explain_fingerprint_mismatch(pa, pb)
        assert len(diffs) == 1
        assert "const[0]" in diffs[0] and "VALUE differs" in diffs[0]
        # identical captures -> identical fingerprint, no diffs
        assert analysis.explain_fingerprint_mismatch(
            pa, _captured_scalar_plan(0.1)) == []

    def test_mesh_keyed_leg_warns_on_donated_multilevel_plan(self):
        """A donated executable spanning >= 2 replica levels is keyed by a
        mesh elastic events can change — flagged, pointing at the elastic
        split."""
        plan, _ = nested_plan()
        report = plan.analyze(donate_argnums=(0,))
        warns = report.by_code("retrace/mesh-keyed-leg")
        assert len(warns) == 1
        assert warns[0].severity == "warning"
        assert "elastic" in warns[0].message
        # no donation -> no hazard (nothing pins the old mesh's buffers)
        assert not plan.analyze().by_code("retrace/mesh-keyed-leg")
        # flat single-level plan: elasticity never re-keys its mesh
        fplan, _ = flat_plan()
        assert not fplan.analyze(donate_argnums=(0,)).by_code(
            "retrace/mesh-keyed-leg"
        )

    def test_fingerprint_parts_define_the_fingerprint(self):
        """The decomposition must reproduce plan_fingerprint's exact byte
        stream (the executable cache keys on it)."""
        import hashlib

        plan, _ = scan_round_plan()
        h = hashlib.sha1()
        for _name, data in executor.fingerprint_parts(plan):
            h.update(data)
        assert h.hexdigest() == executor.plan_fingerprint(plan)
        names = [n for n, _ in executor.fingerprint_components(plan)]
        assert names[:6] == [
            "placements", "placement_kinds", "partitioned_invars",
            "partitioned_outvars", "jaxpr", "stage_skeleton",
        ]


# ---------------------------------------------------------------------------
# communication cost
# ---------------------------------------------------------------------------


class TestCommCost:
    def test_flat_reduce_is_all_dcn(self):
        n, d = 8, 16
        plan, _ = flat_plan(n, d)
        cost = plan.comm_cost()
        # broadcast fans a scalar to n groups; reduce collects (n, d) f32
        assert cost.dcn_bytes == n * 4 + n * d * 4
        assert cost.ici_bytes == 0.0

    def test_nested_splits_dcn_ici(self):
        P, m = 2, 4
        plan, _ = nested_plan(P, m)
        cost = plan.comm_cost()
        # clients-level comm rides ICI; only pods-level crosses DCN
        assert cost.dcn_bytes == P * 4 + P * 4  # broadcast@pods + reduce@pods
        assert cost.ici_bytes == P * m * 4 + P * m * 4

    def test_loop_multiplies_trip_count(self):
        plan, _ = scan_round_plan(n=4, length=3)
        cost = plan.comm_cost()
        single = 4 * 4 + 4 * 4  # broadcast + reduce, n=4 f32 scalars
        assert cost.dcn_bytes == 3 * single
        assert all(c.multiplier == 3.0 for c in cost.per_stage)

    def test_while_flags_unknown_trips(self):
        plan, _ = while_pred_comm_plan()
        cost = plan.comm_cost()
        assert cost.unknown_trips
        assert any(f.code == "commcost/unknown-trip" for f in cost.findings)
        # the predicate's comm is itemized under the cond-plan namespace
        assert any("_c_" in c.stage for c in cost.per_stage)

    def test_cond_counts_max_branch(self):
        plan, _ = cond_comm_plan()
        cost = plan.comm_cost()
        # the silent branch has no comm; the talking branch is the max
        assert cost.total_bytes > 0
        assert all(c.counted for c in cost.per_stage)

    def test_fused_int8_wire_format(self):
        n, P, d = 8, 2, 512
        plan, _ = fused_hier_plan(n, P, d)
        cost = plan.comm_cost()
        dcn_stages = [c for c in cost.per_stage if c.link == "dcn"]
        assert len(dcn_stages) == 1
        (c,) = dcn_stages
        assert c.wire_format == "int8+scales"
        assert c.wire_bytes == P * (d * 1.0 + (d // PACK_COLS) * 4.0)

    def test_int8_block_pinned_to_pack_cols(self):
        assert commcost.INT8_BLOCK == PACK_COLS

    def test_cross_validate_clean_on_cpu(self):
        plan, _ = flat_plan(8, 32)
        findings = analysis.cross_validate_comm_cost(plan)
        assert not [f for f in findings if f.severity == "error"], [
            str(f) for f in findings]

    def test_cross_validate_catches_skewed_model(self):
        """Fault injection: a >5% model skew must produce a mismatch error
        (proves the cross-check can actually fail)."""
        plan, _ = flat_plan(8, 32)
        findings = analysis.cross_validate_comm_cost(plan, model_scale=1.1)
        errors = [f for f in findings if f.code == "commcost/model-mismatch"]
        no_model = [f for f in findings if f.code == "commcost/no-cost-model"]
        assert errors or no_model  # mismatch, unless backend has no costs

    def test_scan_of_cond_multiplied_and_counted(self):
        plan, _ = scan_of_cond_plan(n=4, length=5)
        cost = plan.comm_cost()
        counted = [c for c in cost.per_stage if c.counted]
        assert counted and all(c.multiplier == 5.0 for c in counted)
        assert all("_b" in c.stage for c in counted)  # inside the branch


# ---------------------------------------------------------------------------
# satellite: the three int8 wire models agree
# ---------------------------------------------------------------------------


class TestCrossPodBytesModel:
    def test_napkin_matches_analyzer_exactly(self):
        n, P, d = 8, 2, 512
        plan, _ = fused_hier_plan(n, P, d)
        static_dcn = plan.comm_cost().dcn_bytes
        napkin = drjax.cross_pod_bytes(
            4.0 * d, n=n, num_supergroups=P, compress="int8")
        assert napkin["hierarchical_bytes"] == static_dcn

    def test_int8_ratio_includes_scale_overhead(self):
        # NOT the naive 0.25: one f32 scale per 256-block rides along
        assert drjax.int8_wire_ratio() == (1.0 + 4.0 / PACK_COLS) / 4.0
        assert drjax.int8_wire_ratio() > 0.25

    def test_consistent_with_tpcomm_wire_math(self):
        """models/tpcomm ships one f32 scale per ROW of d values — i.e. the
        same formula with block=d."""
        t, d, m = 128, 4096, 8
        expected = (m - 1) / m * t * (4.0 * d) * drjax.int8_wire_ratio(
            block=d)
        assert tpcomm.int8_wire_bytes(t, d, m) == pytest.approx(expected)

    def test_compress_ratio_still_supported(self):
        a = drjax.cross_pod_bytes(1024.0, n=64, num_supergroups=4,
                                  compress_ratio=0.5)
        assert a["hierarchical_bytes"] == 4 * 1024.0 * 0.5

    def test_unknown_scheme_raises(self):
        with pytest.raises(ValueError, match="unknown compress scheme"):
            drjax.cross_pod_bytes(1.0, n=2, num_supergroups=1,
                                  compress="fp4")


# ---------------------------------------------------------------------------
# lint framework
# ---------------------------------------------------------------------------


def _write(root, rel, content):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        fh.write(textwrap.dedent(content))


class TestLints:
    def test_repo_is_clean(self):
        assert run_lints() == []

    def test_unknown_rule_raises(self):
        with pytest.raises(KeyError, match="no-such-rule"):
            run_lints(rules=["no-such-rule"])

    def test_compat_surface_rule(self, tmp_path):
        root = str(tmp_path)
        # assembled so THIS test file never contains the banned substrings
        banned = "Axis" + "Type"
        _write(root, "src/repro/models/bad.py", f"x = jax.{banned}.Auto\n")
        _write(root, "src/repro/compat/ok.py", f"x = jax.{banned}.Auto\n")
        vs = run_lints(root=root, rules=["compat-surface"])
        assert [v.path for v in vs] == ["src/repro/models/bad.py"]

    def test_compat_surface_rejects_jax_internals(self, tmp_path):
        root = str(tmp_path)
        internal = "jax." + "_src"  # assembled: this file must not match
        _write(root, "src/repro/core/bad.py",
               f"from {internal} import core\n"
               f"x = 1  # {internal} mentioned beside compat\n")
        _write(root, "src/repro/compat/jaxpr.py", f"from {internal} import core\n")
        _write(root, "src/repro/compat_notes.py", f"import {internal}\n")
        vs = run_lints(root=root, rules=["compat-surface"])
        assert [(v.path, v.line) for v in vs] == [
            ("src/repro/compat_notes.py", 1),
            ("src/repro/core/bad.py", 1),
            ("src/repro/core/bad.py", 2),
        ]

    def test_donate_jit_rule_and_marker(self, tmp_path):
        root = str(tmp_path)
        _write(root, "src/repro/algorithms/bad.py", """\
            import jax
            step = jax.jit(lambda s: s)
        """)
        _write(root, "src/repro/algorithms/ok.py", """\
            import jax
            step = jax.jit(lambda s: s, donate_argnums=(0,))
            # no-donate: serving path, params reused across calls
            serve = jax.jit(lambda s: s)
        """)
        vs = run_lints(root=root, rules=["donate-jit"])
        assert [(v.path, v.line) for v in vs] == [
            ("src/repro/algorithms/bad.py", 2)]
        assert "donate the carried state" in vs[0].message

    def test_no_version_branch_rule(self, tmp_path):
        root = str(tmp_path)
        _write(root, "src/repro/runtime/bad.py", """\
            import jax
            NEW = jax.__version__ >= "0.5"
        """)
        _write(root, "src/repro/compat/meshes.py", """\
            import jax
            NEW = jax.__version__ >= "0.5"
        """)
        vs = run_lints(root=root, rules=["no-version-branch"])
        assert [v.path for v in vs] == ["src/repro/runtime/bad.py"]

    def test_jit_of_plan_rule(self, tmp_path):
        root = str(tmp_path)
        _write(root, "src/repro/core/bad.py", """\
            import jax
            fast = jax.jit(lambda x: x)
        """)
        _write(root, "src/repro/launch/bad2.py", """\
            import jax
            fast = jax.jit(run_plan, donate_argnums=(0,))
        """)
        _write(root, "src/repro/runtime/executor.py", """\
            import jax
            fast = jax.jit(run_plan)
        """)
        vs = run_lints(root=root, rules=["jit-of-plan"])
        assert sorted(v.path for v in vs) == [
            "src/repro/core/bad.py", "src/repro/launch/bad2.py"]

    def test_mesh_axes_literal_rule(self, tmp_path):
        root = str(tmp_path)
        _write(root, "src/repro/runtime/bad.py", """\
            AXES = ("pod", "data")
        """)
        _write(root, "src/repro/launch/mesh.py", """\
            REPLICA_AXES = ("pod", "data")
            DEEP = ("superpod", "pod", "data")
        """)
        _write(root, "src/repro/models/ok.py", """\
            spec = ("batch", "model")
            one = ("data",)
        """)
        vs = run_lints(root=root, rules=["mesh-axes-literal"])
        assert [(v.path, v.line) for v in vs] == [
            ("src/repro/runtime/bad.py", 1)]
        assert "launch/mesh.py" in vs[0].message

    def test_suppression_marker(self, tmp_path):
        root = str(tmp_path)
        _write(root, "src/repro/core/bad.py", """\
            import jax
            # lint: disable=jit-of-plan
            fast = jax.jit(lambda x: x)
        """)
        assert run_lints(root=root, rules=["jit-of-plan"]) == []

    def test_cli_json_output(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
             "--json"],
            capture_output=True, text=True, check=True,
        )
        report = json.loads(out.stdout)
        assert report["ok"] and report["violations"] == []
        assert set(report["rules"]) >= {"compat-surface", "donate-jit"}

    def test_check_donation_shim(self):
        out = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "check_donation.py")],
            capture_output=True, text=True, check=True,
        )
        assert out.stdout.strip() == "donation lint: OK"

    def test_lints_importable_without_jax(self):
        """The lint CLI path must not load JAX (it runs before the suite)."""
        code = (
            "import sys; sys.path.insert(0, 'src');"
            "from repro.analysis import lints;"
            "assert 'jax' not in sys.modules, 'lints dragged in jax'"
        )
        subprocess.run(
            [sys.executable, "-c", code], cwd=REPO, check=True)


# ---------------------------------------------------------------------------
# report surface
# ---------------------------------------------------------------------------


class TestReportSurface:
    def test_to_json_roundtrip(self):
        plan, _ = fused_hier_plan()
        report = plan.analyze()
        blob = json.loads(report.to_json())
        assert blob["ok"] is True
        assert blob["comm_cost"]["dcn_bytes"] == report.comm_cost.dcn_bytes

    def test_warnings_do_not_flip_ok(self):
        plan = _captured_scalar_plan(0.5)
        report = plan.analyze()
        assert report.ok and report.warnings
