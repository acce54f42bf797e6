"""Shared scaffolding for the C graph-builder examples: compile the C
host against the native library and run it to emit the frontend IR."""

import os
import subprocess

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.abspath(os.path.join(_HERE, *[os.pardir] * 2))


def compile_and_emit(c_basename: str, tmpdir: str) -> str:
    """Build examples/c/<c_basename> and run it; returns the IR path."""
    from flexflow_tpu.native import load_native

    if load_native() is None:
        raise SystemExit("native toolchain unavailable")
    exe = os.path.join(tmpdir, os.path.splitext(c_basename)[0])
    ir = os.path.join(tmpdir, "model.ir")
    lib_dir = os.path.join(_ROOT, "native", "build")
    subprocess.run([os.environ.get("CC", "cc"),
                    os.path.join(_HERE, c_basename),
                    "-L" + lib_dir, "-lflexflow_tpu_native", "-o", exe],
                   check=True)
    env = dict(os.environ)
    env["LD_LIBRARY_PATH"] = os.pathsep.join(
        p for p in (lib_dir, env.get("LD_LIBRARY_PATH")) if p)
    subprocess.run([exe, ir], check=True, env=env)
    return ir


def compile_and_run_serve(c_basename: str, ok_marker: str,
                          extra_args=()) -> str:
    """Build libflexflow_tpu_serve, compile a C serving main against it
    (plus libpython), run it with the repo root (plus ``extra_args``),
    and assert the marker. Shared by run_incr_decoding.py /
    run_spec_infer.py."""
    import sysconfig

    lib_dir = os.path.join(_ROOT, "native", "build")
    subprocess.run(["make", "-C", os.path.join(_ROOT, "native")],
                   check=True, capture_output=True)
    pylib = "python" + sysconfig.get_config_var("LDVERSION")
    pylibdir = sysconfig.get_config_var("LIBDIR")
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        exe = os.path.join(td, os.path.splitext(c_basename)[0])
        subprocess.run([os.environ.get("CC", "cc"),
                        os.path.join(_HERE, c_basename),
                        "-L" + lib_dir, "-lflexflow_tpu_serve",
                        "-L" + pylibdir, "-l" + pylib, "-o", exe],
                       check=True)
        env = dict(os.environ)
        env["LD_LIBRARY_PATH"] = os.pathsep.join(
            p for p in (lib_dir, pylibdir, env.get("LD_LIBRARY_PATH"))
            if p)
        # a host that dies says where: its interpreter dumps the threads'
        # stacks on a fatal signal and the failure below carries them
        env.setdefault("PYTHONFAULTHANDLER", "1")
        # the embedded interpreter picks its backend from JAX_PLATFORMS
        out = subprocess.run([exe, _ROOT, *extra_args], env=env,
                             capture_output=True, text=True)
        if out.returncode != 0:
            raise RuntimeError(
                f"{c_basename} exited {out.returncode}\nstdout:\n"
                f"{out.stdout[-2000:]}\nstderr:\n{out.stderr[-6000:]}")
        assert ok_marker in out.stdout, out.stdout
        return out.stdout.strip()
