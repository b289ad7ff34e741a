"""Build and load the C++ helpers under ``native/`` for THIS host.

The kernels are compiled with ``-march=native``, so a library is only
valid on a CPU like the one that built it — and checkouts get copied
between machines with their build products.  The output path therefore
carries a fingerprint of the source text and of this host's CPU
(``native/build/<stem>.<fingerprint>.so``): a library built from other
source or on another kind of CPU has another name and is never looked
at, and a checkout without the library builds it from the ``.cc`` git
tracks.  No compiler: callers fall back to their Python paths.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
from typing import Optional

_NATIVE = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "native")


def _host_cpu() -> str:
    """What ``-march=native`` keys on: the CPU model and its feature
    flags (first processor entry of /proc/cpuinfo), else the best
    ``platform`` can say."""
    try:
        with open("/proc/cpuinfo", encoding="utf-8") as f:
            lines = f.read().split("\n\n", 1)[0].splitlines()
        keep = [ln for ln in lines
                if ln.split(":", 1)[0].strip() in
                ("vendor_id", "cpu family", "model", "model name",
                 "stepping", "flags", "Features", "CPU implementer",
                 "CPU part")]
        if keep:
            return platform.machine() + "\n" + "\n".join(keep)
    except OSError:
        pass
    return f"{platform.machine()}|{platform.processor()}|{platform.node()}"


def lib_path(src_name: str, stem: str) -> str:
    with open(os.path.join(_NATIVE, src_name), "rb") as f:
        src = f.read()
    fp = hashlib.sha256(src + b"\0" + _host_cpu().encode()).hexdigest()[:16]
    return os.path.join(_NATIVE, "build", f"{stem}.{fp}.so")


def load(src_name: str, stem: str) -> Optional[ctypes.CDLL]:
    """The library for ``native/<src_name>``, built on first use;
    None when the source or a working compiler is missing."""
    try:
        so = lib_path(src_name, stem)
    except OSError:
        return None
    if not os.path.exists(so):
        os.makedirs(os.path.dirname(so), exist_ok=True)
        tmp = f"{so}.{os.getpid()}.tmp"
        try:
            subprocess.run(
                ["g++", "-O3", "-march=native", "-shared", "-fPIC",
                 "-o", tmp, os.path.join(_NATIVE, src_name)],
                check=True, capture_output=True, timeout=120)
            os.replace(tmp, so)      # atomic: racing builders agree
        except (OSError, subprocess.SubprocessError):
            return None
    try:
        return ctypes.CDLL(so)
    except OSError:
        return None
