"""Training-Program serialization round-trip (reference
`python/paddle/static/io.py` save/load + `fluid/framework.py:5383`
program-desc serialization): a recorded Program — ops, params, optimizer
request, optimizer state — survives the process and continues training."""
import os
import subprocess
import sys
import textwrap

import numpy as np

import paddle_tpu as paddle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(lr=0.1):
    main = paddle.static.Program()
    startup = paddle.static.Program()
    with paddle.static.program_guard(main, startup):
        x = paddle.static.data("x", [None, 8], "float32")
        y = paddle.static.data("y", [None, 1], "float32")
        h = paddle.static.nn.fc(x, 16, activation="relu")
        out = paddle.static.nn.fc(h, 1)
        loss = ((out - y) * (out - y)).mean()
        opt = paddle.optimizer.Adam(learning_rate=lr)
        opt.minimize(loss)
    return main, startup, loss


def _feeds(n):
    rng = np.random.default_rng(9)
    return [{"x": rng.normal(size=(8, 8)).astype(np.float32),
             "y": rng.normal(size=(8, 1)).astype(np.float32)}
            for _ in range(n)]


def _run(main, startup, loss, feeds, skip_startup=False):
    exe = paddle.static.Executor()
    if not skip_startup:
        exe.run(startup)
    return [float(exe.run(main, feed=f, fetch_list=[loss])[0])
            for f in feeds]


class TestProgramSerialization:
    def test_same_process_round_trip_continues(self, tmp_path):
        paddle.enable_static()
        try:
            feeds = _feeds(4)
            paddle.seed(17)
            paddle.static.global_scope().vars.clear()
            main, startup, loss = _build()
            base = _run(main, startup, loss, feeds)  # uninterrupted 4

            paddle.seed(17)
            paddle.static.global_scope().vars.clear()
            main2, startup2, loss2 = _build()
            first = _run(main2, startup2, loss2, feeds[:2])
            prefix = str(tmp_path / "ckpt")
            paddle.static.save(main2, prefix)

            paddle.static.global_scope().vars.clear()
            prog = paddle.static.load_program(prefix)
            loss_var = prog.vars[loss2.name]
            rest = _run(prog, None, loss_var, feeds[2:], skip_startup=True)
            np.testing.assert_allclose(first + rest, base, rtol=1e-5,
                                       atol=1e-6)
        finally:
            paddle.disable_static()

    def test_cross_process_continue(self, tmp_path):
        paddle.enable_static()
        try:
            feeds = _feeds(4)
            paddle.seed(23)
            paddle.static.global_scope().vars.clear()
            main, startup, loss = _build()
            base = _run(main, startup, loss, feeds)

            paddle.seed(23)
            paddle.static.global_scope().vars.clear()
            main2, startup2, loss2 = _build()
            _run(main2, startup2, loss2, feeds[:2])
            prefix = str(tmp_path / "ckpt")
            paddle.static.save(main2, prefix)
            loss_name = loss2.name
        finally:
            paddle.disable_static()

        child = textwrap.dedent(f"""
            import numpy as np
            import paddle_tpu as paddle
            paddle.enable_static()
            prog = paddle.static.load_program({prefix!r})
            loss = prog.vars[{loss_name!r}]
            rng = np.random.default_rng(9)
            feeds = [{{"x": rng.normal(size=(8, 8)).astype(np.float32),
                       "y": rng.normal(size=(8, 1)).astype(np.float32)}}
                     for _ in range(4)]
            exe = paddle.static.Executor()
            for f in feeds[2:]:
                print("LOSS", float(exe.run(prog, feed=f,
                                            fetch_list=[loss])[0]))
        """)
        script = tmp_path / "resume.py"
        script.write_text(child)
        env = dict(os.environ)
        env.update({"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"})
        r = subprocess.run([sys.executable, str(script)], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        got = [float(ln.split()[1]) for ln in r.stdout.splitlines()
               if ln.startswith("LOSS")]
        np.testing.assert_allclose(got, base[2:], rtol=1e-5, atol=1e-6)
