"""The static-analysis gate itself: every check fires on its seeded
fixture (exactly once), the clean tree reports zero unsuppressed
findings, and the baseline machinery is strict about malformed input.

The fixtures under tests/fixtures/analysis/ are the analyzer's unit
corpus: jaxpr_violations.py is traced abstractly (never executed),
host_violations.py is linted AST-only (never imported).
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import host_lint
from repro.analysis.findings import (ALL_CHECKS, HL_LOOP_NUMERIC,
                                     HL_LOOP_SYNC, HL_TRACED_MUT,
                                     HL_TRACED_RAISE, HL_UNANNOTATED,
                                     JX_COMPILE_CACHE, JX_HOSTCALL,
                                     JX_PACKED_CAST, JX_PAGE_TILE,
                                     JX_TILE_DIVIDE, JX_VMEM, Finding,
                                     load_baseline, split_suppressed)
from repro.analysis.jaxpr_audit import (DEFAULT_VMEM_BUDGET, ProgramSpec,
                                        audit_program, call_signature)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "analysis")


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.fixture(scope="module")
def jaxpr_fixture():
    spec = importlib.util.spec_from_file_location(
        "jaxpr_violations", os.path.join(FIXTURES, "jaxpr_violations.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------- jaxpr
# one seeded program per check — each must fire its check exactly once,
# and nothing else (a second finding means a check is over-firing; an
# empty list means it is dead)

JX_CASES = [
    ("hostcall", [((4,), jnp.float32)], {}, DEFAULT_VMEM_BUDGET,
     JX_HOSTCALL),
    # same check, different container: proves the auditor descends into
    # shard_map bodies (the TP serving programs), not only pjit cores
    ("shard_map_hostcall", [((4,), jnp.float32)], {}, DEFAULT_VMEM_BUDGET,
     JX_HOSTCALL),
    ("packed_cast", [((8, 16), jnp.int8)], {}, DEFAULT_VMEM_BUDGET,
     JX_PACKED_CAST),
    ("tile_misdivide", [((48, 16), jnp.float32)], {}, DEFAULT_VMEM_BUDGET,
     JX_TILE_DIVIDE),
    ("page_tile_mismatch", [((4, 16, 16), jnp.int8)],
     {"page_size": 16}, DEFAULT_VMEM_BUDGET, JX_PAGE_TILE),
    # whole-array f32 blocks, in and out, each double-buffered:
    # 4 * 256*256*4 = 1 MiB > the 256 KiB test budget (and well under
    # the default budget, so only JX105 distinguishes this case)
    ("vmem_hog", [((256, 256), jnp.float32)], {}, 256 * 1024, JX_VMEM),
]


@pytest.mark.parametrize("fn,argspec,kw,budget,check",
                         JX_CASES, ids=[c[4] for c in JX_CASES])
def test_jaxpr_check_fires_exactly_once(jaxpr_fixture, fn, argspec, kw,
                                        budget, check):
    args = tuple(_sds(s, d) for s, d in argspec)
    spec = ProgramSpec(fn, getattr(jaxpr_fixture, fn), [args], **kw)
    findings, n_sig = audit_program(spec, vmem_budget=budget)
    assert [f.check for f in findings] == [check], \
        [f.format() for f in findings]
    assert n_sig == 1
    assert findings[0].program == fn


def test_page_tile_check_fires_on_head_split(jaxpr_fixture):
    """JX104 also catches whole-page tiles that split the lane axis."""
    spec = ProgramSpec("page_head_split", jaxpr_fixture.page_head_split,
                       [(_sds((4, 16, 16), jnp.int8),)], page_size=16)
    findings, _ = audit_program(spec)
    assert [f.check for f in findings] == [JX_PAGE_TILE], \
        [f.format() for f in findings]


@pytest.mark.parametrize("dims,dtype,want", [
    ((16, 64), jnp.int8, 32 * 128),          # int8 sublane tile is 32
    ((1, 16, 256), jnp.int8, 32 * 256),
    ((8, 128), jnp.float32, 8 * 128 * 4),    # already tile-aligned
    ((64, 1), jnp.float32, 64 * 128 * 4),    # a column takes 128 lanes
    ((4, 8, 64), jnp.bfloat16, 4 * 16 * 128 * 2),
])
def test_vmem_tile_bytes_pads_to_tpu_tiles(dims, dtype, want):
    from repro.analysis.jaxpr_audit import vmem_tile_bytes
    assert vmem_tile_bytes(dims, dtype) == want


def test_compile_cache_check_fires_exactly_once(jaxpr_fixture):
    spec = ProgramSpec(
        "shape_polymorphic", jaxpr_fixture.shape_polymorphic,
        [(_sds((4,), jnp.float32),), (_sds((8,), jnp.float32),)])
    findings, n_sig = audit_program(spec)
    assert [f.check for f in findings] == [JX_COMPILE_CACHE]
    assert n_sig == 2


def test_call_signature_is_jit_cache_identity():
    a = (jnp.float32, (4, 2))
    sig = lambda *args, **kw: call_signature(args, kw or None)
    x, y = _sds((4, 2), jnp.float32), _sds((4, 2), jnp.float32)
    assert sig(x, 3) == sig(y, 3)                    # same shapes/statics
    assert sig(x, 3) != sig(_sds((8, 2), jnp.float32), 3)   # shape
    assert sig(x, 3) != sig(_sds((4, 2), jnp.int32), 3)     # dtype
    assert sig(x, 3) != sig(x, 4)                    # static arg value
    assert sig(x, steps=3) != sig(x, 3)              # tree structure
    del a


# ----------------------------------------------------------------- host

def test_each_host_check_fires_exactly_once():
    rel = "tests/fixtures/analysis/host_violations.py"
    findings = host_lint.lint_file(
        os.path.join(FIXTURES, "host_violations.py"), rel)
    assert sorted(f.check for f in findings) == [
        HL_LOOP_NUMERIC, HL_LOOP_SYNC, HL_TRACED_MUT, HL_TRACED_RAISE,
        HL_UNANNOTATED], [f.format() for f in findings]
    assert all(f.file == rel and f.line > 0 for f in findings)


def test_module_without_annotation_is_flagged_wholesale(tmp_path):
    p = tmp_path / "unannotated.py"
    p.write_text("import jax\n\nfast = jax.jit(lambda x: x)\n")
    findings = host_lint.lint_file(str(p))
    assert [f.check for f in findings] == [HL_UNANNOTATED]


def test_every_check_id_is_covered_by_a_fixture():
    """The seeded corpus spans the full check catalog — adding a check
    without a fixture fails here, not silently in CI."""
    seeded = {c[4] for c in JX_CASES} | {
        JX_COMPILE_CACHE, HL_LOOP_NUMERIC, HL_LOOP_SYNC, HL_TRACED_MUT,
        HL_TRACED_RAISE, HL_UNANNOTATED}
    assert seeded == set(ALL_CHECKS)


# ------------------------------------------------------------- baseline

def test_baseline_round_trip(tmp_path):
    p = tmp_path / "baseline.toml"
    p.write_text(
        '# reviewed\n'
        '[[suppress]]\n'
        'check = "JX106"\n'
        'contains = "decode_replay"\n'
        'reason = "replay retraces per recorded-token count by design"\n')
    sups = load_baseline(str(p))
    assert len(sups) == 1
    hit = Finding("JX106", "a.py", 1, "decode_replay", "2 signatures")
    miss = Finding("JX106", "a.py", 1, "prefill_chunk", "2 signatures")
    live, muted = split_suppressed([hit, miss], sups)
    assert muted == [hit] and live == [miss]


@pytest.mark.parametrize("body,err", [
    ('[[suppress]]\ncheck = "JX101"\n', "reason"),      # no justification
    ('[[suppress]]\nreason = "x"\n', "check"),          # no check
    ('[[suppress]]\ncheck = JX101\nreason = "x"\n', "double-quoted"),
    ('[[suppress]]\ncheck = "JX101"\nreason = "x"\nfoo = "y"\n',
     "unknown"),
    ('what is this\n', "unparseable"),
], ids=["no-reason", "no-check", "unquoted", "unknown-key", "garbage"])
def test_malformed_baseline_is_a_hard_error(tmp_path, body, err):
    p = tmp_path / "baseline.toml"
    p.write_text(body)
    with pytest.raises(ValueError, match=err):
        load_baseline(str(p))


# ------------------------------------------------------------ clean tree

def test_clean_tree_reports_zero_unsuppressed_findings():
    """The CI gate, as an importable assertion: both engines over the
    real tree and shipped baseline — nothing fires."""
    from repro.analysis import run_all
    live, muted, counters = run_all()
    assert live == [], [f.format() for f in live]
    assert muted == []                   # shipped baseline is empty
    assert counters["programs_traced"] >= 10
    per = counters["jaxprs_per_program"]
    assert per["prefill_chunk"] == 1 and per["decode_step.paged"] == 1
