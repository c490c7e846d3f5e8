"""Build driver for the native components: g++ -> libec_<name>.so.

The reference ships its native codecs as autotools/cmake targets producing
libec_*.so under <libdir>/erasure-code (loaded by ErasureCodePluginRegistry
at runtime); here a single g++ invocation produces the same artifact shape
next to the sources, rebuilt unless a sidecar stamp shows it was built from
this very source and these flags. No compiler -> None, and callers surface
the reference's dlopen error path; a prebuilt library is never loaded in
place of a build.
"""

from __future__ import annotations

import hashlib
import os
import shutil
import subprocess

NATIVE_DIR = os.path.dirname(os.path.abspath(__file__))

#: the reference's naming contract: PLUGIN_PREFIX "libec_" PLUGIN_SUFFIX ".so"
PLUGIN_PREFIX = "libec_"
PLUGIN_SUFFIX = ".so"


def plugin_path(name: str, directory: str | None = None) -> str:
    return os.path.join(
        directory or NATIVE_DIR, f"{PLUGIN_PREFIX}{name}{PLUGIN_SUFFIX}"
    )


def _stamp(source: str, cmd: list[str]) -> str:
    """sha256 of the source and the compile flags: what a library must
    have been built from to be reused."""
    h = hashlib.sha256()
    with open(source, "rb") as f:
        h.update(f.read())
    h.update("\0".join(cmd).encode())
    return h.hexdigest()


def _compile(out: str, source: str, cmd: list[str]) -> None:
    """Build `out` from `source` with `cmd` (flags, no -o) unless its
    sidecar stamp says it already was. A library copied in from elsewhere,
    or built from an older source, has no matching stamp and is rebuilt,
    never loaded. Writes go through temporaries, so concurrent builders
    never see a half-written library. Raises CalledProcessError when the
    compiler fails."""
    stamp = _stamp(source, cmd)
    try:
        with open(out + ".sha256") as f:
            if f.read() == stamp and os.path.exists(out):
                return
    except FileNotFoundError:
        pass
    tmp = f"{out}.{os.getpid()}.tmp"
    subprocess.run(
        [*cmd, "-o", tmp, source], check=True, capture_output=True, text=True
    )
    os.replace(tmp, out)
    with open(tmp, "w") as f:
        f.write(stamp)
    os.replace(tmp, out + ".sha256")


def build_shared(name: str, source: str) -> str | None:
    """Compile a standalone helper .so (crc32c etc.); returns the path or
    None without a toolchain or when the compile fails."""
    cc = shutil.which("cc") or shutil.which("gcc") or shutil.which("g++")
    if cc is None:
        return None
    out = os.path.join(NATIVE_DIR, f"lib{name}.so")
    try:
        _compile(out, source, [cc, "-O3", "-shared", "-fPIC"])
    except subprocess.CalledProcessError:
        return None
    return out


def build_plugin(
    name: str = "native",
    source: str | None = None,
    directory: str | None = None,
) -> str | None:
    """Compile `source` into libec_<name>.so; returns the path or None when
    no toolchain is available. Rebuilds unless the stamp matches."""
    cxx = shutil.which("g++") or shutil.which("c++")
    if cxx is None:
        return None
    source = source or os.path.join(NATIVE_DIR, "ec_plugin.cpp")
    out = plugin_path(name, directory)
    from ceph_tpu import __version__

    cmd = [
        cxx, "-O3", "-shared", "-fPIC", "-std=c++17",
        f'-DCEPH_TPU_PLUGIN_VERSION="ceph-tpu-{__version__}"',
    ]
    try:
        _compile(out, source, cmd)
    except subprocess.CalledProcessError as e:
        # never fall back silently to a stale .so: surface the diagnostics
        raise RuntimeError(f"building {out} failed:\n{e.stderr}") from None
    return out
