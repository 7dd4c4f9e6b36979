"""``scripts/kernel_bundles.py`` reads libtpu's per-bundle utilization
dump: here on files written in its format, without libtpu."""

import importlib.util
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "scripts" / "kernel_bundles.py"


@pytest.fixture(scope="module")
def kb():
    spec = importlib.util.spec_from_file_location("kernel_bundles", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def write(path, rows):
    path.write_text(
        "== CAPACTIY:\n"
        "MXU, XLU, VALU, EUP, VLOAD, VLOAD:FILL, VSTORE, VSTORE:SPILL, SALU\n"
        "    4     3     4     1     3     3     1     1     2\n"
        "== UTILIZATION:\n" + "".join(" ".join(map(str, r)) + "\n"
                                       for r in rows))


def test_a_unit_is_busy_in_the_bundles_that_use_it(kb, tmp_path):
    f = tmp_path / "u.txt"
    write(f, [[4, 0, 2, 0, 0, 0, 1, 1, 0],
              [1, 3, 0, 0, 2, 2, 0, 0, 1],
              [0, 0, 0, 0, 0, 0, 0, 0, 0],
              [0, 1, 4, 1, 0, 0, 1, 0, 0]])
    u = kb.utilization(f)
    assert u["bundles"] == 4
    assert u["MXU"] == 50.0 and u["XLU"] == 50.0 and u["VALU"] == 50.0
    assert u["EUP"] == 25.0 and u["VSTORE"] == 50.0
    assert u["VSTORE:SPILL"] == 25.0 and u["VLOAD:FILL"] == 25.0


@pytest.mark.parametrize("name, kernel", [
    ("dsa_attn_fwd.1", "dsa_attn_fwd"),
    ("jvp_dsa_attn_fwd_.1", "dsa_attn_fwd"),
    ("transpose_jvp_dsa_attn_bwd_dq__.1", "dsa_attn_bwd_dq"),
])
def test_a_kernel_is_found_by_the_name_of_its_call(kb, tmp_path, name,
                                                   kernel):
    for prog in (name, "copy.3", "TLP", "<late-finalization>"):
        write(tmp_path / f"17923-{prog}-69{kb.FINAL}", [[1] * 9])
    (tmp_path / f"17923-{name}-67-pre-delay_hlo-static.txt").write_text("")
    found = kb.kernel_files(tmp_path, ["dsa_index", kernel])
    assert list(found) == [name]
