"""Fixtures of the benchmark's CPU tests: a checkout root holding the
smoke cells, and a helper that runs one cell through the harness on the
host CPU (the harness's look for a chip is skipped) and parses its
result line."""
import json

import jax
import pytest

from bench.tests.smoke_cells import write_root


@pytest.fixture(scope="module")
def smoke_root(tmp_path_factory):
    return write_root(str(tmp_path_factory.mktemp("checkout")))


@pytest.fixture
def run_smoke(capsys):
    from bench.run import run_cell

    def run(root, cell, seed=2 ** 31 + 3, seconds=2.0):
        assert run_cell(cell, seed, seconds, False, root=root,
                        devices=jax.devices("cpu")[:1]) == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return run
