"""Tier-1 tests for the static verifier (src/repro/analysis/).

Three angles: (1) every seeded violation fixture trips its stable CC code
(the same suite ``python -m repro.analysis --fixtures`` runs); (2) honest
inputs — the shipped merges, the real kv hot-path shape, the scheduled
manifests — lint clean; (3) the component checks (HLO walk vs manifest,
alias-map parsing, record-key dedup, report suppression) behave on
hand-built inputs.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (CATALOG, Diagnostic, Report, audit_plan,
                            certify_merge_fn, check_commit_walk,
                            check_donation, check_kv_tick_taint,
                            check_noncommit_region, check_noncommit_walk)
from repro.analysis.cli import fixture_checks
from repro.analysis.placement import aliased_param_numbers
from repro.core import ccache
from repro.core.merge_functions import ADD, standard_merges
from repro.launch.hlo_cost import analyze_hlo
from repro.serve.kv import serving_plan

S32 = jax.ShapeDtypeStruct
AXIS = "shards"


# ---------------------------------------------------------------------------
# seeded violations: every fixture must trip its CC code
# ---------------------------------------------------------------------------


_FIXTURES = fixture_checks()


@pytest.mark.parametrize("name,code,thunk", _FIXTURES,
                         ids=[f[0] for f in _FIXTURES])
def test_fixture_trips_its_code(name, code, thunk):
    diags = thunk()
    assert any(d.code == code for d in diags), (
        f"seeded violation {name!r} did not trip {code}: "
        f"{[d.format() for d in diags]}")
    for d in diags:
        assert d.code in CATALOG


# ---------------------------------------------------------------------------
# honest inputs lint clean
# ---------------------------------------------------------------------------


def test_shipped_merges_certify_clean():
    for fn in standard_merges():
        diags = certify_merge_fn(fn, site=f"merge:{fn.name}")
        assert not diags, (
            f"{fn.name}: declared traits refuted: "
            f"{[d.format() for d in diags]}")


def test_pure_scatter_region_is_collective_free():
    def scatter(table, keys, vals):
        return table.at[keys].add(vals)

    avals = (S32((16, 2), jnp.int32), S32((4,), jnp.int32),
             S32((4, 2), jnp.int32))
    assert check_noncommit_region(scatter, AXIS, 8, avals, "t") == []


def test_kv_hot_path_shape_is_taint_free():
    # the fully deferred due=0 tick: scatter into pendings[0], settled
    # passes through untouched
    def tick(settled, pendings, keys, vals):
        return settled, (pendings[0].at[keys].add(vals),) + pendings[1:]

    tbl = S32((16, 2), jnp.int32)
    diags = check_kv_tick_taint(tick, AXIS, 8, tbl, (tbl, tbl),
                                S32((4,), jnp.int32),
                                S32((4, 2), jnp.int32), "t")
    assert diags == []


def test_serving_plans_audit_clean():
    for defer in ("all", "top", "none"):
        plan = serving_plan(8, defer)
        assert audit_plan(plan, 8, merge_fn=ADD, site=defer) == []


# ---------------------------------------------------------------------------
# scheduled manifests (the placement lint's ground truth)
# ---------------------------------------------------------------------------


def test_serving_manifest_round_counts():
    plan = serving_plan(8, "all")
    full = ccache.collective_manifest(plan, 8, merge_fn=ADD)
    assert [m.name for m in full] == ["chip", "host", "pod"]
    # chip: stride-1 ADD fuses into one all-reduce; host/pod are lane
    # stages: 1 exchange round + log2(stride) gather rounds
    assert full[0].kind == "fused" and full[0].fused_ops == 1
    assert full[0].permute_rounds == 0
    assert full[1].permute_rounds == 2
    assert full[2].permute_rounds == 3


def test_program_manifest_prefix():
    plan = serving_plan(8, "all")
    assert ccache.program_manifest(plan, 8, 0, merge_fn=ADD) == []
    for due in (1, 2, 3):
        prog = ccache.program_manifest(plan, 8, due, merge_fn=ADD)
        assert len(prog) == due
    with pytest.raises(ValueError):
        ccache.program_manifest(plan, 8, 4, merge_fn=ADD)


# ---------------------------------------------------------------------------
# HLO walk vs manifest
# ---------------------------------------------------------------------------


_CLEAN_HLO = """\
HloModule m, num_partitions=8

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (p0: f32[64,2]) -> f32[64,2] {
  %p0 = f32[64,2] parameter(0)
  ROOT %ar = f32[64,2] all-reduce(%p0), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
}
"""


def _fused_manifest(fused_ops=1):
    return [ccache.StageManifest(index=0, name="chip", defer=False,
                                 stride=1, fanout=8, kind="fused",
                                 fused_ops=fused_ops, exchange_rounds=0,
                                 intra_rounds=0)]


def test_commit_walk_matches_manifest():
    w = analyze_hlo(_CLEAN_HLO, level_sizes=(8,), level_names=("chip",))
    assert check_commit_walk(w, _fused_manifest(), "t") == []


def test_commit_walk_flags_count_mismatch():
    w = analyze_hlo(_CLEAN_HLO, level_sizes=(8,), level_names=("chip",))
    diags = check_commit_walk(w, _fused_manifest(fused_ops=2), "t")
    assert any(d.code == "CC021" and "all-reduce count" in d.message
               for d in diags)


def test_noncommit_walk_flags_any_collective():
    w = analyze_hlo(_CLEAN_HLO, level_sizes=(8,), level_names=("chip",))
    diags = check_noncommit_walk(w, "t")
    assert [d.code for d in diags] == ["CC020"]


def test_empty_manifest_means_noncommit():
    w = analyze_hlo(_CLEAN_HLO, level_sizes=(8,), level_names=("chip",))
    assert any(d.code == "CC020" for d in check_commit_walk(w, [], "t"))


# ---------------------------------------------------------------------------
# analyze_hlo input validation (level vector vs partition product)
# ---------------------------------------------------------------------------


def test_analyze_hlo_rejects_level_product_mismatch():
    with pytest.raises(ValueError, match="num_partitions"):
        analyze_hlo(_CLEAN_HLO, level_sizes=(2, 2),
                    level_names=("chip", "host"))


def test_analyze_hlo_rejects_name_size_length_mismatch():
    with pytest.raises(ValueError, match="level_names"):
        analyze_hlo(_CLEAN_HLO, level_sizes=(2, 4), level_names=("chip",))


# ---------------------------------------------------------------------------
# donation / alias-map parsing
# ---------------------------------------------------------------------------


def test_alias_map_brace_matching_ignores_lookalikes():
    hlo = (
        "HloModule m, input_output_alias={ {0}: (0, {}, may-alias), "
        "{1}: (2, {0}) }\n\n"
        "ENTRY %main (p0: f32[4]) -> f32[4] {\n"
        "  %p0 = f32[4] parameter(0)\n"
        "  ROOT %c = f32[4] custom-call(%p0), "
        "output_to_operand_aliasing={{0}: (9, {})}\n"
        "}\n")
    # the custom-call's look-alike attr must NOT contribute param 9
    assert aliased_param_numbers(hlo) == {0, 2}


def test_check_donation_missing_map_downgrades_without_require():
    hlo = "HloModule m\n\nENTRY %main (p0: f32[4]) -> f32[4] {\n}\n"
    diags = check_donation(hlo, {0}, "t", require=False)
    assert [d.severity for d in diags] == ["warning"]
    hard = check_donation(hlo, {0}, "t", require=True)
    assert [d.severity for d in hard] == ["error"]


# ---------------------------------------------------------------------------
# report mechanics: suppression and severity
# ---------------------------------------------------------------------------


def _d(code="CC021", site="kv[all]:tick[due=1]", severity="error"):
    return Diagnostic(code=code, site=site, message="x", severity=severity)


def test_report_suppression_by_code_and_site():
    r = Report(suppressions=("CC021@kv[all]",))
    r.add(_d())
    r.add(_d(site="kv[top]:tick[due=1]"))
    assert len(r.failures()) == 1 and not r.ok()
    r2 = Report(suppressions=("CC021",))
    r2.add(_d())
    r2.add(_d(site="kv[top]:tick[due=1]"))
    assert r2.ok()


def test_report_warnings_do_not_fail():
    r = Report()
    r.add(_d(code="CC022", severity="warning"))
    assert r.ok() and len(r.diagnostics) == 1
    assert "CC022" in r.format()


def test_unknown_code_rejected():
    with pytest.raises(ValueError):
        Diagnostic(code="CC999", site="t", message="x")


# ---------------------------------------------------------------------------
# XOR (HPCC RandomAccess): certified on bit patterns, software exchange
# ---------------------------------------------------------------------------


def test_verifier_certifies_xor_and_refutes_a_mislabeled_one():
    import dataclasses
    from repro.core.merge_functions import BITWISE_XOR
    assert certify_merge_fn(BITWISE_XOR) == []
    idem = dataclasses.replace(BITWISE_XOR, name="xor_idem", idempotent=True)
    assert any(d.code == "CC001" for d in certify_merge_fn(idem))
    scal = dataclasses.replace(BITWISE_XOR, name="xor_scal", scalable=True)
    assert any(d.code == "CC002" for d in certify_merge_fn(scal))


def test_xor_manifest_is_the_software_butterfly():
    """XOR has no fused collective: the four-chip serving plan's innermost
    stage is a one-round butterfly, where ADD fuses into an all-reduce."""
    from repro.core.merge_functions import BITWISE_XOR
    plan = serving_plan(4, "all")
    xor = ccache.collective_manifest(plan, 4, merge_fn=BITWISE_XOR)
    add = ccache.collective_manifest(plan, 4, merge_fn=ADD)
    assert [m.name for m in xor] == [m.name for m in add]
    assert xor[0].kind == "butterfly" and xor[0].fused_ops == 0
    assert xor[0].permute_rounds == 1
    assert add[0].kind == "fused" and add[0].permute_rounds == 0
    assert xor[1:] == add[1:]
