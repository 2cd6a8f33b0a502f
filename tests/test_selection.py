"""Selection-heuristic tests incl. hypothesis property tests (paper §3.2 ②)."""

import hypothesis.strategies as st
import pytest
from hypothesis import given, settings

from repro.core import GenConfig
from repro.core.model import CorpusIR, GenerationResult, ImplDef, ParamDef, PrimitiveDef
from repro.core.select import SelectGPO, choose, score, valid_candidates


def _prim(defs):
    return PrimitiveDef(
        name="p", group="g", brief="", parameters=(ParamDef("a"),),
        returns_ctype="register", definitions=tuple(defs))


def _impl(target="t", ctypes=("float32",), flags=(), body="return a",
          native=True):
    return ImplDef(target_extension=target, ctypes=tuple(ctypes),
                   flags=tuple(flags), implementation=body, is_native=native)


def test_flag_subset_required():
    prim = _prim([_impl(flags=("xla", "exotic"))])
    assert valid_candidates(prim, "t", "float32", frozenset({"xla"})) == []
    assert len(valid_candidates(prim, "t", "float32",
                                frozenset({"xla", "exotic"}))) == 1


def test_more_flags_wins():
    """Paper: more hardware capabilities used => more specialized => wins."""
    generic = _impl(flags=("xla",), body="return a  # generic")
    special = _impl(flags=("xla", "mxu", "vmem"), body="return a  # special")
    sel = choose(_prim([generic, special]), "t", "float32",
                 frozenset({"xla", "mxu", "vmem"}))
    assert sel.impl is special
    assert sel.candidates == 2


def test_loc_tiebreak_shortest_wins():
    """Paper: equal score -> ascending lines of code, first (shortest) wins."""
    long_ = _impl(flags=("xla",), body="x = a\ny = x\nreturn y")
    short = _impl(flags=("xla",), body="return a")
    sel = choose(_prim([long_, short]), "t", "float32", frozenset({"xla"}))
    assert sel.impl is short


def test_hardware_override_changes_selection():
    """Paper §4.1: the generator can be 'tricked' into assuming hardware."""
    generic = _impl(flags=("xla",))
    special = _impl(flags=("xla", "bmi2"), body="return a  # pext")
    prim = _prim([generic, special])
    assert choose(prim, "t", "float32", frozenset({"xla"})).impl is generic
    assert choose(prim, "t", "float32",
                  frozenset({"xla", "bmi2"})).impl is special


@settings(max_examples=200, deadline=None)
@given(
    hw=st.frozensets(st.sampled_from("abcdefgh"), max_size=8),
    impls=st.lists(
        st.tuples(st.frozensets(st.sampled_from("abcdefgh"), max_size=5),
                  st.integers(1, 5)),
        min_size=1, max_size=6),
)
def test_selection_invariants(hw, impls):
    """Invariants: (1) chosen impl's flags ⊆ hw; (2) no valid candidate has a
    strictly higher score; (3) among max-score candidates none is shorter."""
    defs = [_impl(flags=tuple(sorted(f)), body="\n".join(["return a"] * loc))
            for f, loc in impls]
    prim = _prim(defs)
    sel = choose(prim, "t", "float32", hw)
    cands = valid_candidates(prim, "t", "float32", hw)
    if not cands:
        assert sel is None
        return
    assert frozenset(sel.impl.flags) <= hw
    best = max(score(c, hw) for c in cands)
    assert score(sel.impl, hw) == best
    assert sel.impl.loc == min(c.loc for c in cands if score(c, hw) == best)


def test_non_native_selection_warns():
    """Paper §3.2: non-native workaround => build-time warning (Fig 6)."""
    from repro.core.model import TargetDef

    tgt = TargetDef(
        name="t", vendor="v", flags=("xla",), ctypes=("float32",),
        default_ctype="float32", lanes=128, sublanes=8, mxu=(128, 128),
        vmem_bytes=1, hbm_bytes=1, peak_flops_bf16=1.0, hbm_bw=1.0,
        ici_bw=1.0, ici_links=1)
    corpus = CorpusIR.from_defs(
        targets={"t": tgt},
        primitives={"p": _prim([_impl(flags=("xla",), native=False)])})
    ctx = GenerationResult(config=GenConfig(target="t"), corpus=corpus)
    SelectGPO().run(ctx)
    assert any("non-native workaround" in w for w in ctx.warnings)


def test_cherry_pick_closes_over_test_deps(lib_cpu):
    """Paper §1 'slim library': only= subset + transitive test requirements."""
    from repro.core import load_library

    lib = load_library("cpu_xla", only=("range_count",))
    prims = set(lib.PRIMITIVES)
    assert "range_count" in prims
    # range_count's test requires between_inclusive, hadd, select, set1, load
    assert {"between_inclusive", "hadd", "select", "set1", "load"} <= prims
    # but unrelated primitives are absent (slim)
    assert "flash_attention" not in prims
    assert "wkv6_scan" not in prims


@pytest.mark.parametrize("kind,want", [("TPU v5 lite", "tpu_v5e"),
                                       ("TPU v5", None), ("TPU v4", None)])
def test_live_target_maps_exact_tpu_kinds(monkeypatch, kind, want):
    """auto resolves a TPU by its exact device kind; a kind without a UPD
    target is an error, never a library tuned for another chip."""
    import types

    from repro.core import hwprobe

    monkeypatch.setattr(hwprobe.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(hwprobe.jax, "devices",
                        lambda: [types.SimpleNamespace(device_kind=kind)])
    if want is None:
        with pytest.raises(RuntimeError, match="no UPD target"):
            hwprobe.live_target()
    else:
        assert hwprobe.live_target() == want
