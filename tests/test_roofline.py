"""Roofline HLO-parser unit tests: dot FLOPs, collective bytes, flat loop
trip-correction (nested "wide" scans must not compound)."""
import textwrap

from repro.launch.roofline import parse_hlo, Roofline


HLO = textwrap.dedent("""\
    HloModule test

    %body.1 (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
      %w = f32[16,32]{1,0} parameter(0)
      %x = f32[8,16]{1,0} parameter(1)
      %dot.1 = f32[8,32]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %ag = f32[8,64]{1,0} all-gather(%dot.1), dimensions={1}
    }

    %body.outer (q: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
      %inner = (s32[], f32[8,16]) while(%q), condition=%cond.1, body=%body.1
    }

    ENTRY %main (a: f32[8,16]) -> f32[8,16] {
      %a = f32[8,16]{1,0} parameter(0)
      %r = (s32[], f32[8,16]) while(%a), condition=%cond.2, body=%body.outer
      %ar = f32[8,16]{1,0} all-reduce(%a), to_apply=%sum
    }
    """)


def test_dot_flops_and_flat_trips():
    st = parse_hlo(HLO, loop_trips=10)
    # dot: 2 * (8*32) * 16 = 8192 flops, x10 (flat — NOT x100 for nesting)
    assert st.dot_flops == 8192 * 10
    assert st.n_dots == 1
    assert st.n_while == 2


def test_collective_bytes():
    st = parse_hlo(HLO, loop_trips=10)
    # all-gather result 8*64*4 = 2048 B x10; all-reduce 8*16*4 x2 (ring) x1
    assert st.per_op["all-gather"] == 2048 * 10
    assert st.per_op["all-reduce"] == 8 * 16 * 4 * 2
    assert st.collective_bytes == 2048 * 10 + 1024


def test_roofline_terms():
    r = Roofline(chips=256, flops=197e12 * 256, hbm_bytes=819e9 * 256,
                 collective_bytes=50e9 * 256, model_flops_=197e12 * 128)
    assert abs(r.compute_s - 1.0) < 1e-9
    assert abs(r.memory_s - 1.0) < 1e-9
    assert abs(r.collective_s - 1.0) < 1e-9
    assert abs(r.useful_ratio - 0.5) < 1e-9
    assert r.dominant in ("compute", "memory", "collective")


def test_peak_rates_keyed_by_device_kind():
    """One table of published peaks; a kind missing from it is an error."""
    import pytest
    from repro.core.costmodel import TARGET, TARGET_KIND, peak_rates
    v5e = peak_rates("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw, v5e.ici_bw) == (197e12, 819e9, 50e9)
    assert "TPU v5e" in v5e.source
    assert peak_rates(TARGET_KIND) is TARGET
    with pytest.raises(KeyError, match="no published peak rates"):
        peak_rates("cpu")
