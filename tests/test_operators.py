import os

import numpy as np
import pytest

from ytk_mp4j_tpu.exceptions import Mp4jError
from ytk_mp4j_tpu.operators import Operator, Operators
from ytk_mp4j_tpu.operands import Operands
from ytk_mp4j_tpu.utils import native


ALL_OPS = [Operators.SUM, Operators.PROD, Operators.MAX, Operators.MIN]
NP_REF = {
    "SUM": np.add,
    "PROD": np.multiply,
    "MAX": np.maximum,
    "MIN": np.minimum,
}


@pytest.mark.parametrize("op", ALL_OPS, ids=lambda o: o.name)
@pytest.mark.parametrize("operand", Operands.NUMERIC, ids=lambda o: o.name)
def test_identity(op, operand):
    ident = op.identity(operand.dtype)
    x = np.array([3, 1, 2], dtype=operand.dtype)
    got = op.np_fn(np.full_like(x, ident), x)
    np.testing.assert_array_equal(got, x)


@pytest.mark.parametrize("op", ALL_OPS, ids=lambda o: o.name)
@pytest.mark.parametrize("operand", Operands.NUMERIC, ids=lambda o: o.name)
def test_reduce_into_matches_numpy(op, operand, rng):
    if operand.dtype.kind == "f":
        a = rng.standard_normal(257).astype(operand.dtype)
        b = rng.standard_normal(257).astype(operand.dtype)
    else:
        a = rng.integers(1, 5, 257).astype(operand.dtype)
        b = rng.integers(1, 5, 257).astype(operand.dtype)
    expect = NP_REF[op.name](a, b)
    acc = a.copy()
    native.reduce_into(op, acc, b)
    np.testing.assert_array_equal(acc, expect)


def test_native_backend_is_active():
    # The image has g++; the C++ hot loop must actually be in use.
    native._load()
    assert native.HAVE_NATIVE


def test_native_library_keyed_on_sources_flags_and_cpu(tmp_path,
                                                       monkeypatch):
    """A library built from other sources, with other flags or on
    another CPU is never loaded: its file name does not match."""
    srcs = []
    for name in ("a.cpp", "b.cpp"):
        f = tmp_path / name
        f.write_text(f"// {name}\n")
        srcs.append(str(f))
    monkeypatch.setattr(native, "_SRCS", srcs)
    base = native._so_path()
    assert base == native._so_path()                    # stable
    assert os.path.dirname(base) == native._BUILD_DIR
    (tmp_path / "b.cpp").write_text("// b.cpp, edited\n")
    os.utime(srcs[1], (0, 0))       # contents decide, not the mtime
    edited = native._so_path()
    assert edited != base
    monkeypatch.setattr(native, "_FLAGS", native._FLAGS + ["-DX"])
    flagged = native._so_path()
    assert flagged not in (base, edited)
    monkeypatch.setattr(native, "_cpu_features", lambda: "flags : other")
    assert native._so_path() not in (base, edited, flagged)


def test_custom_operator():
    absmax = Operator.custom("ABSMAX",
                             lambda x, y: np.where(np.abs(x) >= np.abs(y), x, y),
                             0.0)
    a = np.array([-5.0, 1.0, 2.0])
    b = np.array([3.0, -4.0, -1.0])
    got = absmax(a, b)
    np.testing.assert_array_equal(got, [-5.0, -4.0, 2.0])
    acc = a.copy()
    native.reduce_into(absmax, acc, b)  # falls back to np_fn
    np.testing.assert_array_equal(acc, [-5.0, -4.0, 2.0])


def test_by_name():
    assert Operators.by_name("sum") is Operators.SUM
    with pytest.raises(Mp4jError):
        Operators.by_name("nope")
