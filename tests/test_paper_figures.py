"""Smoke test of ``examples/paper_figures.py``: each simulation figure
prints its CSV rows, and every row's value is finite."""
import importlib.util
import math
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "examples" / "paper_figures.py"

# figure -> the row-name prefix and the number of rows it prints
FIGURES = {
    "fig10_inner_scheduling": ("fig10_threads_", 4),
    "fig12_exec_time": ("fig12", 7),
    "fig15_comm_balance": ("fig15_m", 4),
}


@pytest.fixture(scope="module")
def paper_figures():
    spec = importlib.util.spec_from_file_location("paper_figures", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("figure", sorted(FIGURES))
def test_figure_emits_finite_rows(paper_figures, figure, capsys):
    getattr(paper_figures, figure)()
    rows = [line.split(",", 2) for line in
            capsys.readouterr().out.splitlines()]
    prefix, count = FIGURES[figure]
    assert len(rows) == count
    for name, value, derived in rows:
        assert name.startswith(prefix)
        assert math.isfinite(float(value)) and float(value) > 0
        for field in derived.replace(" ", ";").split(";"):
            number = field.partition("=")[2]
            if number != "see_csv":
                assert math.isfinite(float(number)), (name, field)
