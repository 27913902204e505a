"""``correct`` comes out false when the timed path is broken underneath,
and for the control: the float8 reference in the engine's place.

The chip check is skipped; the rest of a run is driven at test size on
the CPU.  The faults a served cell can have: a decode step that returns
its state unchanged, and a token altered where it is produced.  (A batch
half left out and an exchange between chips left out are faults of
training and of sharded cells; these cells have neither.)
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import cell  # noqa: E402
import chipbench_fixture  # noqa: E402
import verdict  # noqa: E402

BIG_SEED = 2 ** 35 + 11


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    base = tmp_path_factory.mktemp("cells")
    return base, chipbench_fixture.write(base)


def state_unchanged(engine):
    inner = engine._decode

    def decode(p, tok, cache, idx):
        logits, _ = inner(p, tok, jax.tree.map(jnp.copy, cache), idx)
        return logits, cache

    engine._decode = decode


def token_altered(engine):
    inner = engine._sample_rows
    vocab = engine.model.cfg.vocab_size
    engine._sample_rows = lambda lg, u, p: (inner(lg, u, p) + 1) % vocab


@pytest.mark.parametrize("name", ["tiny2.open", "tiny1.closed"])
@pytest.mark.parametrize("fault", [state_unchanged, token_altered])
def test_a_broken_timed_path_is_not_correct(tree, name, fault):
    base, bench = tree
    res = cell.run_cell(name, BIG_SEED, 2.0, False, base=base, bench=bench,
                        require_tpu=False, engine_hook=fault)
    assert res["correct"] is False
    assert res["check"]["gap"]["value"] > res["check"]["gap"]["limit"]


def test_an_altered_token_is_caught_by_its_score(tree):
    base, bench = tree
    sv = cell.serve("tiny2.open", BIG_SEED, 2.0, False, base=base,
                    bench=bench, require_tpu=False, engine_hook=token_altered)
    _, nums = cell.check(sv)
    # The logits the tokens were drawn from are right; the tokens not.
    limit = chipbench_fixture.LIMITS["gap"]
    assert nums["logit_err"] < limit < nums["token_gap"]


@pytest.mark.parametrize("name", ["tiny2.open", "tiny1.closed"])
def test_the_float8_control_is_not_correct(tree, name):
    base, bench = tree
    sv = cell.serve(name, BIG_SEED, 2.0, False, base=base, bench=bench,
                    require_tpu=False)
    _, served = cell.check(sv)
    _, control = cell.check(sv, control=True)
    assert verdict.judge(served, chipbench_fixture.LIMITS)
    assert not verdict.judge(control, chipbench_fixture.LIMITS)
    assert control["gap"] >= 3 * served["gap"]
