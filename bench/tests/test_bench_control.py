"""The control, at a size a test run can hold: the plain reference computed
one precision below the configuration's (fp8 operands for a bfloat16
model) and put in the program's place makes a whole run come out not
correct, where the program's own served tokens on the same seed pass."""
import json

import harness
import tiny


def _run(root, capsys, control):
    rc = harness.main(["--workload", "tiny-cell", "--seed", "2147483801",
                       "--seconds", "3", "--trace", "0"], root=root,
                      bench_dir=root / "bench", require_tpu=False,
                      control=control)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_fp8_control_fails_where_the_program_passes(tmp_path, capsys):
    root = tiny.make(tmp_path)
    program = _run(root, capsys, None)
    control = _run(root, capsys, "fp8")
    assert program["correct"] is True and control["correct"] is False
    limit = tiny.LIMITS["worst_gap"]
    p = program["compared"]["worst_gap"]["value"]
    c = control["compared"]["worst_gap"]["value"]
    assert p <= limit < c and c >= 3 * p
