"""C inference API (csrc/inference_capi) — reference
paddle/fluid/inference/capi_exp/pd_inference_api.h surface. Builds a real
C client binary, links libptinfer_capi.so (embedded-CPython → StableHLO/XLA
predictor core), runs it against a saved artifact, and checks the numbers
match the in-process Python predictor."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as paddle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

C_CLIENT = textwrap.dedent("""
    #include "pt_inference_c.h"
    #include <stdio.h>
    #include <stdlib.h>
    #include <string.h>

    int main(int argc, char** argv) {
      if (argc < 2) return 2;
      PD_Config* cfg = PD_ConfigCreate();
      PD_ConfigSetModel(cfg, argv[1], NULL);
      PD_Predictor* pred = PD_PredictorCreate(cfg);
      if (!pred) { fprintf(stderr, "create: %s\\n", PD_GetLastError());
                   return 3; }
      if (PD_PredictorGetInputNum(pred) != 1) return 4;
      const char* in_name = PD_PredictorGetInputName(pred, 0);

      float data[12];
      for (int i = 0; i < 12; ++i) data[i] = (float)i * 0.25f;
      int64_t shape[2] = {3, 4};
      if (PD_PredictorSetInput(pred, in_name, data, shape, 2,
                               PD_DTYPE_FLOAT32) != 0) {
        fprintf(stderr, "set_input: %s\\n", PD_GetLastError());
        return 5;
      }
      if (PD_PredictorRun(pred) != 0) {
        fprintf(stderr, "run: %s\\n", PD_GetLastError());
        return 6;
      }
      const char* out_name = PD_PredictorGetOutputName(pred, 0);
      int64_t oshape[8]; size_t ndim = 0;
      if (PD_PredictorGetOutputShape(pred, out_name, oshape, 8, &ndim)
          != 0) return 7;
      size_t elems = 1;
      for (size_t i = 0; i < ndim; ++i) elems *= (size_t)oshape[i];
      float* out = (float*)malloc(elems * sizeof(float));
      if (PD_PredictorCopyOutput(pred, out_name, out,
                                 elems * sizeof(float)) != 0) return 8;
      printf("shape");
      for (size_t i = 0; i < ndim; ++i) printf(" %lld", (long long)oshape[i]);
      printf("\\n");
      for (size_t i = 0; i < elems; ++i) printf("%.6f\\n", out[i]);
      free(out);
      PD_PredictorDestroy(pred);
      PD_ConfigDestroy(cfg);
      return 0;
    }
""")


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("capi")
    paddle.enable_static()
    try:
        main = paddle.static.Program()
        startup = paddle.static.Program()
        with paddle.static.program_guard(main, startup):
            x = paddle.static.data("x", [-1, 4], "float32")
            h = paddle.static.nn.fc(x, 8, activation="relu")
            y = paddle.static.nn.fc(h, 2)
        exe = paddle.static.Executor()
        exe.run(startup)
        exe.run(main, feed={"x": np.zeros((3, 4), np.float32)},
                fetch_list=[y])
        prefix = str(tmp_path / "model")
        paddle.static.save_inference_model(prefix, [x], [y], exe,
                                           program=main)
        return prefix
    finally:
        paddle.disable_static()


def test_c_client_matches_python(artifact, tmp_path):
    # expected output via the in-process Python predictor
    from paddle_tpu import inference

    pred = inference.create_predictor(inference.Config(artifact))
    feed = (np.arange(12, dtype=np.float32) * 0.25).reshape(3, 4)
    h = pred.get_input_handle(pred.get_input_names()[0])
    h.copy_from_cpu(feed)
    pred.run()
    expected = pred.get_output_handle(
        pred.get_output_names()[0]).copy_to_cpu()

    # build the C client (and the .so if this checkout hasn't built it yet)
    paddle.sysconfig.ensure_native_built("libptinfer_capi.so")
    src = tmp_path / "client.c"
    src.write_text(C_CLIENT)
    binary = tmp_path / "client"
    subprocess.run(
        ["gcc", "-o", str(binary), str(src),
         f"-I{REPO}/csrc/include",
         f"-L{REPO}/paddle_tpu/lib", "-lptinfer_capi",
         f"-Wl,-rpath,{REPO}/paddle_tpu/lib"],
        check=True, capture_output=True)

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run([str(binary), artifact], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    lines = proc.stdout.strip().splitlines()
    shape = tuple(int(v) for v in lines[0].split()[1:])
    values = np.array([float(v) for v in lines[1:]],
                      np.float32).reshape(shape)
    assert shape == tuple(expected.shape)
    np.testing.assert_allclose(values, np.asarray(expected), rtol=1e-5,
                               atol=1e-6)
