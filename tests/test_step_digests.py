"""The witness that a PR left a configuration's program alone: parameter
tree and lowered gradient program of each of the benchmark's nine
configurations, at its tiny size (``tests/configurations.py``) in float32
and in bfloat16, against what the parent commit gave
(``tests/step_digests.py`` -> ``tests/data/step_digests.json``). No
AOT-cache key and no ``trace_lower_s`` of an accepted cell moves under a
PR that passes; one that means to move a program regenerates the file
and says so."""

import json
import os

import configurations
import pytest
import step_digests

with open(
    os.path.join(configurations.ROOT, "tests", "data", "step_digests.json")
) as _f:
    ON_RECORD = json.load(_f)


@pytest.mark.parametrize(
    "case",
    [
        f"{name}/{dtype}"
        for name in configurations.NAMES
        for dtype in step_digests.DTYPES
    ],
)
def test_a_configuration_is_the_program_on_record(case):
    """Each case computes its own digest, so a failure names the
    configuration that moved."""
    assert step_digests.digest(*case.split("/")) == ON_RECORD[case]
