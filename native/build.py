"""Build the native runtime library (g++ -O3 -shared).

Reference analog: the in-tree native build (udf-examples CMakeLists /
the cudf native jar) — here a single g++ invocation; callers fall back to
pure python when the toolchain is unavailable.
"""
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
SOURCES = [os.path.join(HERE, "src", "lz4.cpp"),
           os.path.join(HERE, "src", "snappy.cpp"),
           os.path.join(HERE, "src", "parquet_decode.cpp")]
OUT = os.path.join(HERE, "libsrtpu.so")


def build(force: bool = False) -> str:
    if not force and os.path.exists(OUT) and all(
        os.path.getmtime(OUT) >= os.path.getmtime(s) for s in SOURCES
    ):
        return OUT
    # built aside and renamed into place: processes that build at once (a
    # test run's workers) each load a whole library, never a half-written one
    tmp = f"{OUT}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", tmp, *SOURCES]
    try:
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, OUT)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return OUT


if __name__ == "__main__":
    print(build(force="--force" in sys.argv))
