"""C API (native/src/capi.cc + pd_capi.h; ref inference/capi/) and the C
train demo (native/demo/train_demo.c; ref fluid/train/demo).

The inference test compiles a small C client at test time (gcc is in the
image) and checks its output against the same model run directly through
the Python Executor; the train test saves a trainable program (with
backward + SGD ops) via static.save and asserts the C demo's printed losses
decrease.  Both exercise the full C <-> worker pipe protocol.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.static as static
from paddle_tpu.static import layers as L

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(ROOT, "native")
LIB = os.path.join(NATIVE, "build", "libpaddle_tpu_native.so")
DEMO = os.path.join(NATIVE, "build", "train_demo")


def _build_native():
    subprocess.run(["make", "-C", NATIVE, "-s"], check=True,
                   capture_output=True)


@pytest.fixture(scope="module")
def native_built():
    _build_native()
    assert os.path.exists(LIB) and os.path.exists(DEMO)


def _child_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # PREPEND the repo: the embedded interpreter has no cwd fallback on
    # sys.path
    existing = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = ROOT + (os.pathsep + existing if existing else "")
    return env


C_CLIENT = r"""
#include <stdio.h>
#include <string.h>
#include "pd_capi.h"
int main(int argc, char** argv) {
  PD_Predictor* p = PD_PredictorCreate(argv[1], NULL);
  if (!p) { fprintf(stderr, "%s\n", PD_GetLastError()); return 1; }
  float x[3 * 4];
  for (int i = 0; i < 12; ++i) x[i] = 0.125f * i;
  PD_Tensor in; memset(&in, 0, sizeof in);
  snprintf(in.name, PD_MAX_NAME, "x");
  in.dtype = PD_FLOAT32; in.ndim = 2;
  in.shape[0] = 3; in.shape[1] = 4; in.data = x;
  PD_Tensor* out = NULL; int n = 0;
  if (PD_PredictorRun(p, &in, 1, &out, &n) != 0) {
    fprintf(stderr, "%s\n", PD_GetLastError()); return 1;
  }
  printf("%d\n", n);
  for (long long i = 0; i < out[0].shape[0] * out[0].shape[1]; ++i)
    printf("%.6f\n", ((float*)out[0].data)[i]);
  PD_TensorsFree(out, n);
  PD_PredictorDestroy(p);
  return 0;
}
"""


def test_c_inference_matches_python(tmp_path, native_built):
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = L.data("x", [4])
        y = L.fc(x, 2, act="tanh")
    exe = static.Executor()
    exe.run(startup)
    model_dir = str(tmp_path / "m")
    static.save_inference_model(model_dir, ["x"], [y], exe,
                                main_program=main)

    src = tmp_path / "client.c"
    src.write_text(C_CLIENT)
    exe_path = tmp_path / "client"
    subprocess.run(
        ["cc", "-O1", f"-I{NATIVE}/include", str(src), "-o", str(exe_path),
         f"-L{NATIVE}/build", "-lpaddle_tpu_native",
         f"-Wl,-rpath,{NATIVE}/build"], check=True)
    proc = subprocess.run([str(exe_path), model_dir], capture_output=True,
                          text=True, env=_child_env(), timeout=300)
    assert proc.returncode == 0, proc.stderr
    lines = proc.stdout.strip().splitlines()
    assert lines[0] == "1"
    got = np.asarray([float(v) for v in lines[1:]]).reshape(3, 2)

    probe = (0.125 * np.arange(12, dtype=np.float32)).reshape(3, 4)
    ref, = exe.run(main, feed={"x": probe}, fetch_list=[y])
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_c_train_demo_loss_decreases(tmp_path, native_built):
    """The reference's C++-train-from-saved-program contract
    (train/demo/demo_trainer.cc): python saves a program with backward +
    optimizer ops; the C binary drives training steps and the loss drops."""
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = L.data("x", [13])
        y = L.data("y", [1])
        pred = L.fc(x, 1)
        loss = L.mean(L.square_error_cost(pred, y))
        static.optimizer.SGD(learning_rate=0.05).minimize(loss)
    exe = static.Executor()
    exe.run(startup)
    prefix = str(tmp_path / "train_pkg")
    static.save(main, prefix, exe, fetches=[loss])

    proc = subprocess.run([DEMO, prefix, "30"], capture_output=True,
                          text=True, env=_child_env(), timeout=300)
    assert proc.returncode == 0, proc.stderr + proc.stdout
    losses = [float(ln.split()[-1]) for ln in proc.stdout.splitlines()
              if ln.startswith("step ")]
    assert len(losses) == 30
    assert losses[-1] < losses[0] * 0.5, losses[:3] + losses[-3:]


C_INPROC_CLIENT = r"""
#include <stdio.h>
#include <string.h>
#include "pd_capi.h"
int main(int argc, char** argv) {
  /* the reference's IN-PROCESS predictor contract: no worker fork */
  PD_Predictor* p = PD_PredictorCreateInProcess(argv[1]);
  if (!p) { fprintf(stderr, "%s\n", PD_GetLastError()); return 1; }
  float x[3 * 4];
  for (int i = 0; i < 12; ++i) x[i] = 0.125f * i;
  PD_Tensor in; memset(&in, 0, sizeof in);
  snprintf(in.name, PD_MAX_NAME, "x");
  in.dtype = PD_FLOAT32; in.ndim = 2;
  in.shape[0] = 3; in.shape[1] = 4; in.data = x;
  for (int rep = 0; rep < 2; ++rep) {  /* handle survives repeat calls */
    PD_Tensor* out = NULL; int n = 0;
    if (PD_PredictorRun(p, &in, 1, &out, &n) != 0) {
      fprintf(stderr, "%s\n", PD_GetLastError()); return 1;
    }
    if (rep == 1) {
      printf("%d\n", n);
      for (long long i = 0; i < out[0].shape[0] * out[0].shape[1]; ++i)
        printf("%.6f\n", ((float*)out[0].data)[i]);
    }
    PD_TensorsFree(out, n);
  }
  PD_PredictorDestroy(p);
  return 0;
}
"""


def test_c_inprocess_predictor_matches_python(tmp_path, native_built):
    """PD_PredictorCreateInProcess embeds CPython (dlopen'd libpython) and
    runs the model in the SAME process — the reference AnalysisPredictor
    embedding contract, no worker fork (verify with the absence of a
    python child is overkill; same-output parity is the bar)."""
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = L.data("x", [4])
        y = L.fc(x, 2, act="tanh")
    exe = static.Executor()
    exe.run(startup)
    model_dir = str(tmp_path / "m_inproc")
    static.save_inference_model(model_dir, ["x"], [y], exe,
                                main_program=main)

    src = tmp_path / "client_inproc.c"
    src.write_text(C_INPROC_CLIENT)
    exe_path = tmp_path / "client_inproc"
    subprocess.run(
        ["cc", "-O1", f"-I{NATIVE}/include", str(src), "-o", str(exe_path),
         f"-L{NATIVE}/build", "-lpaddle_tpu_native",
         f"-Wl,-rpath,{NATIVE}/build"], check=True)
    proc = subprocess.run([str(exe_path), model_dir], capture_output=True,
                          text=True, env=_child_env(), timeout=600)
    assert proc.returncode == 0, proc.stderr + proc.stdout
    lines = proc.stdout.strip().splitlines()
    assert lines[0] == "1"
    got = np.asarray([float(v) for v in lines[1:]]).reshape(3, 2)
    probe = (0.125 * np.arange(12, dtype=np.float32)).reshape(3, 2 * 2)
    ref, = exe.run(main, feed={"x": probe}, fetch_list=[y])
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_inprocess_from_live_python_interpreter(tmp_path, native_built):
    """Loading the library INTO python via ctypes must reuse the LIVE
    interpreter (EnsurePython's dlsym(RTLD_DEFAULT) path, GILState from a
    python host thread) — the full C entry points are exercised, not the
    python module directly."""
    import ctypes

    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = L.data("x", [4])
        y = L.fc(x, 2)
    exe = static.Executor()
    exe.run(startup)
    model_dir = str(tmp_path / "m_live")
    static.save_inference_model(model_dir, ["x"], [y], exe,
                                main_program=main)

    class PDTensor(ctypes.Structure):
        _fields_ = [("name", ctypes.c_char * 128),
                    ("dtype", ctypes.c_int), ("ndim", ctypes.c_int),
                    ("shape", ctypes.c_longlong * 8),
                    ("data", ctypes.c_void_p)]

    lib = ctypes.CDLL(LIB)
    lib.PD_PredictorCreateInProcess.restype = ctypes.c_void_p
    lib.PD_PredictorCreateInProcess.argtypes = [ctypes.c_char_p]
    lib.PD_PredictorRun.restype = ctypes.c_int
    lib.PD_PredictorRun.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(PDTensor), ctypes.c_int,
        ctypes.POINTER(ctypes.POINTER(PDTensor)),
        ctypes.POINTER(ctypes.c_int)]
    lib.PD_GetLastError.restype = ctypes.c_char_p
    lib.PD_PredictorDestroy.argtypes = [ctypes.c_void_p]

    pred = lib.PD_PredictorCreateInProcess(model_dir.encode())
    assert pred, lib.PD_GetLastError().decode()

    probe = (0.125 * np.arange(12, dtype=np.float32)).reshape(3, 4)
    buf = np.ascontiguousarray(probe)
    t = PDTensor()
    t.name = b"x"
    t.dtype = 0
    t.ndim = 2
    t.shape[0], t.shape[1] = 3, 4
    t.data = buf.ctypes.data_as(ctypes.c_void_p)
    outs = ctypes.POINTER(PDTensor)()
    n = ctypes.c_int(0)
    rc = lib.PD_PredictorRun(pred, ctypes.byref(t), 1, ctypes.byref(outs),
                             ctypes.byref(n))
    assert rc == 0, lib.PD_GetLastError().decode()
    assert n.value == 1
    o = outs[0]
    got = np.ctypeslib.as_array(
        ctypes.cast(o.data, ctypes.POINTER(ctypes.c_float)),
        shape=(o.shape[0], o.shape[1])).copy()
    lib.PD_TensorsFree(outs, n)
    lib.PD_PredictorDestroy(pred)
    ref, = exe.run(main, feed={"x": probe}, fetch_list=[y])
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
