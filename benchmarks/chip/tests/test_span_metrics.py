"""The readers of the spans inside scoring, the cache passes and the
compiles: each reads its own spans' union, and nothing where they are
absent (a program without those spans)."""
import pytest

import run_cell
from layers import Layers

CHECKOUT = run_cell.CHECKOUT

# A 10-s window with the spans of one scored prefetcher, one cache pass
# and two overlapping compile spans (a cache read inside a backend compile).
SPANS = [
    ("trace_gen", 0.0, 3.0),
    ("jax_compile", 0.5, 1.5),
    ("jax_compile", 1.0, 1.2),
    ("demand_sim", 3.0, 4.0),
    ("prefetch.merge", 3.5, 3.6),
    ("prefetch.classify", 3.8, 3.9),
    ("score", 4.0, 9.0),
    ("score.generate[amc]", 4.0, 6.0),
    ("prefetch.merge", 6.0, 6.5),
    ("cache_pass[l2]", 6.5, 8.0),
    ("cache_pass.group", 6.5, 7.0),
    ("cache_pass.device", 7.0, 7.25),
    ("cache_pass.scatter", 7.25, 8.0),
    ("prefetch.classify", 8.0, 8.5),
    ("score.evaluate", 8.5, 9.0),
]
EXPECTED = {
    "score_generate_pct": 20.0,
    "score_evaluate_pct": 5.0,
    "pf_merge_pct": 6.0,
    "pf_classify_pct": 6.0,
    "cache_pass_host_pct": 12.5,
    "cache_pass_device_pct": 2.5,
    "compile_pct": 10.0,
}
OLD_SPANS = [(n, s, e) for n, s, e in SPANS if n in
             ("trace_gen", "demand_sim", "score", "cache_pass[l2]")]


def _reader(name):
    return run_cell.load_cell(CHECKOUT, "bfs-amazon-scaled.fresh").reader(name)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_reader_reads_its_spans_and_nothing_without_them(name):
    read = _reader(name)
    assert read(Layers(window_s=10.0, spans=SPANS)) == pytest.approx(EXPECTED[name])
    assert read(Layers(window_s=10.0, spans=OLD_SPANS)) is None
    assert read(Layers(window_s=10.0, spans=[])) is None


def test_the_layers_they_split_keep_their_values():
    """The existing readers match whole names, so the new spans nested in
    their layers leave them as they were."""
    for name in ("score_host_pct", "cache_pass_pct", "trace_gen_pct"):
        read = _reader(name)
        assert read(Layers(window_s=10.0, spans=SPANS)) == pytest.approx(
            read(Layers(window_s=10.0, spans=OLD_SPANS))
        )
