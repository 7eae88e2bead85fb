"""The warm-up's reach: which device updates a Sync's changed cells run."""

import pytest

from benchmark.harness.loop import buckets_between, scatter_bucket


@pytest.mark.parametrize("changed,size,want", [
    (0, 1000, None), (1, 1000, 8), (8, 1000, 8), (9, 1000, 16),
    (250, 1000, 256), (251, 1000, "full"), (3, 13, 8), (4, 13, "full")])
def test_scatter_bucket(changed, size, want):
    assert scatter_bucket(changed, size) == want


def test_buckets_between_spans_every_power_of_two():
    assert buckets_between(3000, 9000, 10**6) == {4096, 8192, 16384}
    assert buckets_between(5, 7, 10**6) == {8}
    assert buckets_between(200, 300, 1000) == {256, "full"}
