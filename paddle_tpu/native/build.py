"""Build the native data-path library: python -m paddle_tpu.native.build"""

import os
import subprocess
import sys

_DIR = os.path.dirname(os.path.abspath(__file__))


def build(verbose=True):
    src = os.path.join(_DIR, "src", "dataio.cpp")
    out = os.path.join(_DIR, "libpaddle_tpu_dataio.so")
    tmp = out + f".tmp{os.getpid()}"
    cmd = ["g++", "-O3", "-std=c++17", "-fPIC", "-shared", "-pthread",
           "-Wall", src, "-o", tmp]
    if verbose:
        print(" ".join(cmd))
    subprocess.check_call(cmd)
    os.replace(tmp, out)   # atomic: concurrent builders never see a torn .so
    return out


def _python_flags():
    """Embed flags for THE RUNNING interpreter (a PATH python3-config could
    belong to a different version/ABI than the one importing paddle_tpu)."""
    import sysconfig
    inc = ["-I" + sysconfig.get_path("include")]
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    ver = sysconfig.get_config_var("LDVERSION") or \
        f"{sys.version_info.major}.{sys.version_info.minor}"
    return inc, ([f"-L{libdir}"] if libdir else []) + [f"-lpython{ver}",
                                                      "-ldl", "-lm"]


def build_capi(verbose=True):
    """C inference API (embeds CPython; reference paddle/capi role)."""
    src = os.path.join(_DIR, "src", "capi.cpp")
    out = os.path.join(_DIR, "libpaddle_tpu_capi.so")
    tmp = out + f".tmp{os.getpid()}"
    inc, ld = _python_flags()
    cmd = (["g++", "-O3", "-std=c++17", "-fPIC", "-shared", "-Wall", src]
           + inc + ["-o", tmp] + ld)
    if verbose:
        print(" ".join(cmd))
    subprocess.check_call(cmd)
    os.replace(tmp, out)
    return out


def _source_digest(src):
    import hashlib
    with open(src, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def ensure(which="dataio", verbose=False):
    """Build `which` ('dataio' or 'capi') unless its .so was built from
    the source as it stands.  Freshness is the SOURCE'S DIGEST, recorded
    beside the binary at build time — not mtimes, which a copied or
    freshly checked-out tree does not preserve.  Best-effort: returns the
    .so path on success, None when the toolchain is unavailable or the
    build fails (``why_unavailable`` keeps the reason).  The binaries are
    intentionally NOT committed — they are rebuilt on demand here.
    Disable with PADDLE_TPU_NO_NATIVE_BUILD=1 (e.g. images without g++)."""
    name = {"dataio": "libpaddle_tpu_dataio.so",
            "capi": "libpaddle_tpu_capi.so"}[which]
    if os.environ.get("PADDLE_TPU_NO_NATIVE_BUILD"):
        _FAILED[which] = "PADDLE_TPU_NO_NATIVE_BUILD is set"
        return None
    if which in _FAILED:   # a persistent toolchain failure must not be
        return None        # re-paid per call (e.g. per feeder batch)
    src = os.path.join(_DIR, "src", which + ".cpp")
    out = os.path.join(_DIR, name)
    stamp = out + ".src.sha256"
    try:
        digest = _source_digest(src)
        if os.path.exists(out) and os.path.exists(stamp):
            with open(stamp) as f:
                if f.read().strip() == digest:
                    return out
        path = (build if which == "dataio" else build_capi)(verbose=verbose)
        with open(stamp, "w") as f:
            f.write(digest + "\n")
        return path
    except (OSError, subprocess.CalledProcessError) as e:
        # missing g++/headers: the Python paths keep working
        _FAILED[which] = f"{type(e).__name__}: {e}"
        return None


_FAILED = {}   # lib -> why its build failed this process; see ensure()


def why_unavailable(which="dataio"):
    """Why ``ensure(which)`` returned None this process (None if it
    never failed)."""
    return _FAILED.get(which)


def capi_header_dir():
    return os.path.join(_DIR, "include")


if __name__ == "__main__":
    path = build()
    print("built", path)
    path = build_capi()
    print("built", path)
    sys.exit(0)
