"""The control at a size a test run holds: the plain reference computed in
the precision below the configuration's, put in the program's place,
reads above the cell's limit, where the program's own served tokens read
under it.  The dense cell's control is fp8 and the Mamba2 cell's int8, as
on the chip (`control.py` reads both there, at the cells' own sizes)."""
import jax
import pytest

import chipbench_testkit
from chipbench import check, cli, registry


@pytest.fixture
def tree(tmp_path, monkeypatch):
    monkeypatch.setattr(registry, "BENCH_DIR", chipbench_testkit.make_tree(tmp_path))


@pytest.mark.parametrize("config", ["tiny-dense", "tiny-mamba"])
@pytest.mark.parametrize("seed", [17, 2**31 + 100])
def test_control_fails_where_the_program_passes(tree, config, seed):
    lim = registry.limits(f"{config}.long")["widest_gap"]
    c = cli.Cell.build(config, "tiny-long", seed, jax.devices()[:1])
    rec = c.session.serve(c.waves[0])
    c.session.close()
    _, inputs = c.check_inputs([rec], seed)
    limits = registry.limits(f"{config}.long")
    program = check.served_gaps(c.ref, c.params, c.model, inputs)
    control = check.control_gaps(c.ref, c.params, c.model, inputs, lim["control"])
    assert program["widest_gap"] <= lim["limit"] < control["widest_gap"]
    assert check.verdict(limits, program, 0)[0]
    assert not check.verdict(limits, control, 0)[0]
