"""``BENCHMARK.json`` holds to its contract (ISSUE 30): the benchmark's
own tests are not part of tier-1, so a program PR that breaks the
manifest, a configuration, a reader file or a family has to show here."""

from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def test_the_benchmark_manifest_is_valid():
    from benchmark.lib import validate

    assert validate.check(ROOT) == []
