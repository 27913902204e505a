"""The fit check's arithmetic, steered onto the CPU at test size.

``fitcheck.py`` compiles the engine's window programs for a described
v5e; here the same function compiles them for the CPU device, which has
the same memory analysis, and its byte counts must agree with the
program's own shapes and with ``counts.py``.
"""
import json
import sys
from pathlib import Path

import jax

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import counts  # noqa: E402
import fitcheck  # noqa: E402


def test_fit_counts_the_engine_arrays_at_test_size():
    cfg = json.loads((HERE / "configs" / "mamba2-2.7b.json").read_text())
    cfg.update(d_model=64, n_layer=2, vocab_size=128, d_state=16,
               headdim=16, chunk_size=32, serve={"slots": 4, "chunk": 32})
    res = fitcheck.fit(cfg, jax.devices("cpu")[0])
    assert res["weights_bytes"] == counts.weight_bytes(cfg)
    assert res["decode_view_bytes"] == res["weights_bytes"]
    assert res["arena_bytes"] == 4 * counts.state_bytes(cfg)
    assert res["resident_bytes"] == (2 * res["weights_bytes"] +
                                     3 * res["arena_bytes"])
    assert set(res["programs"]) == {"decode", "prefill_chunk"}
    # The donated state arena is aliased into each program's output.
    for p in res["programs"].values():
        assert p["alias_bytes"] == res["arena_bytes"]
    assert res["peak_bytes"] >= res["resident_bytes"]
    assert res["fits"]
