"""The rest of a run with the timed path broken underneath, and the
control, at a size a test can hold.

The tests skip the harness's look for a chip (``require_chip=False``) and
drive ``run.run_cell`` on the CPU at a tiny ``rows``: the same ``lgb.train``
call, callbacks, window, model read-back, reference and ``judge`` as on the
chip, with the cell's own limits. A sound run is correct. Not correct are

- the control the cell's file names: the reference put in the program's
  place one precision below the one the configuration states, gradients
  and hessians on the 16 levels of four bits for int8
  (``reference.stand_ins``);
- each fault a training cell can have, planted in the program: a step
  that returns its state unchanged (the scores after an iteration are the
  scores before it), half of the batch left out and the mean taken over
  the rest (every other row masked out of the tree's sums), an answer
  altered where it is produced (one leaf value of each tree raised by 5%
  after the scores were updated with the true one);
- the last two planted in the reference put in the program's place too,
  which is how they are read at a cell's own size.

None of these numbers is a device number: the CPU runs the XLA twin of the
kernels. ``median_leaf_value_gap`` reads the rounding noise of the
gradients, which falls with the root of a leaf's rows: at a test's 60k
rows a sound run reads what only 63M rows bring under the cell's limit, so
there the test holds it against the control's reading, not the limit.

On the chip, at a cell's own size, the same call reads the program's
numbers and every stand-in's beside the cell's limits, one line a seed
(the readings PERF.md section 2 sets the limits from):

    python3 benchmarks/tests/test_faults.py <cell> <seed> [<seed> ...]
"""

import json
import os
import sys

import pytest

if __name__ == "__main__":      # pytest's conftest.py does this otherwise
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

import manifest
import run

SEED = 2_147_483_659          # over 2**31, as the driver's seeds are
ROWS = {"higgs-gpu63-int8.train": 60_000}


NOISE = "median_leaf_value_gap"     # see the docstring


def _cell(name):
    cell = manifest.load_cell(name)
    cell.check = dict(cell.check, score_rows=10_000, split_rows=ROWS[name])
    return cell


def _stand_ins(cell):
    return (cell.control, "half_batch", "altered_leaf")


def _run(cell, **kw):
    return run.run_cell(cell, SEED, 0.1, False, require_chip=False,
                        rows=ROWS[cell.name], **kw)


def _values(result):
    return {k: v["value"] for k, v in result["compared"].items()}


@pytest.fixture(scope="module")
def cell():
    return _cell("higgs-gpu63-int8.train")


@pytest.fixture(scope="module", params=sorted(ROWS))
def stood_in(request):
    """One sound run of each cell, and every stand-in judged after it."""
    cell = _cell(request.param)
    return cell, _run(cell, stand_ins=_stand_ins(cell))


def test_a_sound_run_is_correct(stood_in):
    cell, result = stood_in
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_iters_per_s", "setup_s"}
    assert list(result)[-1] == "compared"
    over = {name for name, pair in result["compared"].items()
            if not pair["value"] <= pair["limit"]}
    assert over <= {NOISE}, result["compared"]


def test_the_control_is_not_correct(stood_in):
    cell, result = stood_in
    control = result["stand_ins"][cell.control]
    assert control["correct"] is False, control["compared"]
    assert _values(control)[NOISE] > cell.limits[NOISE]
    assert _values(control)[NOISE] > 3 * _values(result)[NOISE]
    assert _values(control)["leaf_count_gap"] == 0


def test_the_reference_with_half_of_the_batch_left_out(stood_in):
    half = stood_in[1]["stand_ins"]["half_batch"]
    assert half["correct"] is False
    assert _values(half)["leaf_count_gap"] > 0.4


def test_the_reference_with_an_answer_altered(stood_in):
    altered = stood_in[1]["stand_ins"]["altered_leaf"]
    assert altered["correct"] is False
    assert 0.03 < _values(altered)["leaf_value_gap"] < 0.07
    assert _values(altered)["score_gap"] > 1e-3


def test_a_step_that_leaves_its_state_unchanged(cell, monkeypatch):
    from lightgbm_tpu.boosting import GBDT
    step = GBDT.train_one_iter

    def unchanged(self, *a, **kw):
        import jax.numpy as jnp
        self._boost_from_average()
        before = jnp.array(self.scores, copy=True)   # the step donates it
        stop = step(self, *a, **kw)
        self.scores = before
        return stop

    monkeypatch.setattr(GBDT, "train_one_iter", unchanged)
    result = _run(cell)
    assert result["correct"] is False
    assert _values(result)["score_gap"] > 0.5


def test_half_of_the_batch_left_out(cell, monkeypatch):
    import jax.numpy as jnp
    from lightgbm_tpu.boosting import GBDT
    step = GBDT.train_one_iter

    def halved(self, *a, **kw):
        keep = (jnp.arange(self._sample_mask.shape[0]) % 2 == 0)
        self._sample_mask = self._sample_mask * keep
        return step(self, *a, **kw)

    monkeypatch.setattr(GBDT, "train_one_iter", halved)
    result = _run(cell)
    assert result["correct"] is False
    assert _values(result)["leaf_count_gap"] > 0.4


def test_an_answer_altered_where_it_is_produced(cell, monkeypatch):
    from lightgbm_tpu.tree import Tree
    make = Tree.from_arrays.__func__

    def altered(cls, *a, **kw):
        tree = make(cls, *a, **kw)
        tree.leaf_value[-1] *= 1.05
        return tree

    monkeypatch.setattr(Tree, "from_arrays", classmethod(altered))
    result = _run(cell)
    assert result["correct"] is False
    assert _values(result)["leaf_value_gap"] > 0.01


def main(argv) -> int:
    """On the chip, at the cell's own size."""
    cell = manifest.load_cell(argv[1])
    for seed in map(int, argv[2:]):
        result = run.run_cell(cell, seed, 1.0, False,
                              stand_ins=_stand_ins(cell))
        reading = {"cell": cell.name, "seed": seed,
                   "program": {"correct": result["correct"],
                               **_values(result)}}
        for kind, its in result["stand_ins"].items():
            reading[kind] = {"correct": its["correct"], **_values(its)}
        print("READING " + json.dumps(reading), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
