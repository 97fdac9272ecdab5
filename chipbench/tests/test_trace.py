"""The trace reduction: HLO-text event names as a TPU v5e trace gives them,
the kernel events matched to the configuration's layers, and a whole
reduction of a short trace recorded on the chip."""
import gzip
import json
import pathlib

import pytest

from chipbench import counts, spec, trace

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# event names copied from a v5e trace of DIN served under the tpu preset
GATHER = ("%gather_einsum_kernel.2 = f32[100,4096,80]{2,1,0:T(8,128)} "
          "custom-call(s32[65]{0:T(128)S(1)} %and_select_fusion.2, "
          "s32[65]{0:T(128)S(1)} %fusion.2, s32[65]{0:T(128)S(1)} "
          "%compare_convert_fusion.1, f32[4096,18]{1,0:T(8,128)S(1)} "
          "%fusion.3, f32[2,100,18,80]{3,2,1,0:T(8,128)S(1)} %copy.21, "
          "s32[4096,1]{1,0:T(8,128)S(1)} %copy.22), custom_call_target="
          "\"tpu_custom_call\", operand_layout_constraints={s32[65]{0}, "
          "f32[4096,18]{1,0}}")
POOL = ("%gather_einsum_kernel.3 = f32[4096,18]{1,0:T(8,128)S(1)} "
        "custom-call(s32[17]{0:T(128)S(1)} %copy-done.23, s32[17]{0} %a, "
        "s32[17]{0} %b, f32[4096,100]{1,0:T(8,128)S(1)} %fusion.7, "
        "f32[2,100,18]{2,1,0:T(8,128)S(1)} %copy.26, s32[4096,1]{1,0} %c), "
        "custom_call_target=\"tpu_custom_call\"")
MATMUL = ("%mari_matmul_kernel_gather.1 = f32[4096,256]{1,0:T(8,128)S(1)} "
          "custom-call(s32[4096]{0:T(1024)S(1)} %copy-done.3, "
          "f32[4096,512]{1,0:T(8,128)S(1)} %pad.6, f32[512,256]{1,0:T(8,128)"
          "S(1)} %pad.7, f32[2,1,256]{2,1,0:T(1,128)S(1)} %reshape.8), "
          "custom_call_target=\"tpu_custom_call\"")
TESTDATA = pathlib.Path(__file__).resolve().parents[1] / "testdata"
# DIN at published widths (embeddings 18, sequence 100, activation unit
# 80-40, MLP 200-80, context 12): the (layer, K, N) of its MLP's first
# layer and the (layer, L, D, H) of its gathered activation unit
DIN_SITES = {"mari_matmul": [("mlp_0", 18 + 18 + 12, 200)],
             "gather_einsum": [("din_attn", 100, 18, 80)]}


def _sites(name):
    b = spec.load_benchmark()
    ref = spec.load_reference(spec.ROOT, b, name)
    cfg = json.loads((spec.bench_dir(spec.ROOT, b) / "configs" /
                      f"{name}.json").read_text())
    return ref.kernel_sites(cfg)


def test_parse_op_reads_the_instruction_and_shapes():
    inst, out, ops = trace.parse_op(GATHER)
    assert inst == "gather_einsum_kernel"
    assert out == ("f32", (100, 4096, 80))
    assert ("f32", (4096, 18)) in ops and ("f32", (2, 100, 18, 80)) in ops
    assert ("s32", (65,)) in ops
    assert trace.parse_op("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %x)")[:2] \
        == ("fusion", ("f32", (8,)))


def test_kernel_events_count_the_layers_useful_work():
    din = DIN_SITES
    _, out, ops = trace.parse_op(MATMUL)
    # DIN's mlp_0 candidate side is K = 48, N = 200, padded to 512 x 256
    assert trace._mari_matmul_work(out, ops, din["mari_matmul"]) == \
        counts.mari_matmul(4096, 48, 200, 2, True)
    _, out, ops = trace.parse_op(GATHER)
    assert trace._gather_einsum_work(out, ops, din["gather_einsum"]) == \
        counts.gather_einsum(4096, 2, 100, 18, 80)
    _, out, ops = trace.parse_op(POOL)
    assert trace._gather_einsum_work(out, ops, din["gather_einsum"]) == \
        counts.gather_einsum(4096, 2, 100, 18, None)
    # no paper-ranking layer fits DIN's padded 512 x 256 matmul
    _, out, ops = trace.parse_op(MATMUL)
    assert trace._mari_matmul_work(
        out, ops, _sites("paper-ranking")["mari_matmul"]) is None


def test_paper_ranking_layers_fit_their_padded_tiles():
    pr = _sites("paper-ranking")["mari_matmul"]
    rows = 2048

    def event(k, n):
        return trace._mari_matmul_work(
            ("f32", (rows, n)), [("s32", (rows,)), ("f32", (rows, k)),
                                 ("f32", (k, n)), ("f32", (4, 1, n))], pr)

    # experts' fc0: K = 64 + 500 + 500 = 1064 -> 1536, N = 512
    assert event(1536, 512) == counts.mari_matmul(rows, 1064, 512, 4, True)
    # gates: K 1064 -> 1536, N = 4 -> 128
    assert event(1536, 128) == counts.mari_matmul(rows, 1064, 4, 4, True)
    # attn_q (500 x 64) and task fc0 (256 x 128) share the 512 x 128 tile:
    # the smaller work is taken
    assert event(512, 128) == min(counts.mari_matmul(rows, 500, 64, 4, True),
                                  counts.mari_matmul(rows, 256, 128, 4,
                                                     True))


def test_reduction_of_a_trace_recorded_on_the_chip(tmp_path):
    """Against a plain scan of the same file: the busy union, and each
    kernel's event count and summed duration."""
    from jax.profiler import ProfileData

    raw = tmp_path / "t.xplane.pb"
    raw.write_bytes(gzip.decompress(
        (TESTDATA / "din_zipf_sat.xplane.pb.gz").read_bytes()))
    red = trace.reduce(str(raw), DIN_SITES, V5E)

    events = [e for p in ProfileData.from_file(str(raw)).planes
              if p.name.startswith("/device:TPU:")
              for line in p.lines if line.name == "XLA Ops"
              for e in line.events]
    busy, end = 0, 0
    for s, e in sorted((e.start_ns, e.end_ns) for e in events):
        busy += max(0, e - max(s, end))
        end = max(end, e)
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(busy / 1e9, rel=1e-12)
    for prefix, name in (("%mari_matmul_kernel", "mari_matmul"),
                         ("%gather_einsum_kernel", "gather_einsum")):
        mine = [e for e in events if e.name.startswith(prefix)]
        got = red["kernels"][name]
        assert got["events"] == len(mine) > 0
        assert got["unmatched"] == 0
        assert got["seconds"] == pytest.approx(
            sum(e.duration_ns for e in mine) / 1e9, rel=1e-12)
        assert 0 < got["min_seconds"] < got["seconds"]
    top = red["device_ops"]
    assert 0 < len(top) <= 10
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)
    assert sum(t for _, t in red["idle_gaps"]) >= 0
