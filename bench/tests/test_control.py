"""The control (the reference with fp8 matmul operands) reads far above the
program on the same served positions: at smoke sizes, on three seeds, its
smallest reading of each number is at least three times the program's
largest, and under the limits `run.judge` applies it comes out not
correct while the program comes out correct."""
import run
from conftest import smoke_cell


def test_control_separates_from_the_program(cpu_device):
    program, control = [], []
    for seed in (101, 102, 2 ** 31 + 103):
        cell = smoke_cell("granite")
        eng = run.build_engine(cell, seed)
        run.warm_up(eng, cell)
        window = run.Window(eng, cell, seed)
        window.run(1.5)
        del eng
        got = run.check(cell, window, seed, control=True)
        program.append(got["numbers"])
        control.append(got["control"])
    for name in ("logit_gap", "logit_gap_mean"):
        lower = max(p[name] for p in program)
        upper = min(c[name] for c in control)
        assert upper >= 3 * lower, (name, program, control)
        limits = {name: (lower + upper) / 2}
        assert all(run.judge(p, 0, limits)[0] for p in program)
        assert not any(run.judge(c, 0, limits)[0] for c in control)
