"""Host-callback ops on the chip: the print op (ordered `io_callback`
through the real Executor) and a raw `io_callback` under jit.  The CPU
suite covers the same ops in tests/test_ops_tail2.py; this pins that the
TPU runtime's host send/recv carries them too (static/ops_tail2.py module
docstring)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.static as static

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="checks the TPU runtime's host callbacks")


def test_print_op_through_executor_on_chip(capfd):
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        block = main.current_block()
        block.create_var(name="x", shape=(2,), dtype="float32", is_data=True)
        out = block.create_var(name="o")
        block.append_op("print", inputs={"In": ["x"]},
                        outputs={"Out": [out.name]},
                        attrs={"message": "chip-dbg: "})
    exe = static.Executor()
    exe.run(startup)
    x = np.asarray([1.5, 2.5], np.float32)
    (got,) = exe.run(main, feed={"x": x}, fetch_list=[out.name])
    np.testing.assert_allclose(got, x, rtol=1e-6)
    assert "chip-dbg:" in capfd.readouterr().out


def test_ordered_io_callback_under_jit_on_chip():
    from jax.experimental import io_callback

    seen = []

    def host(v):
        seen.append(float(v))
        return np.float32(v * 2)

    @jax.jit
    def f(x):
        y = io_callback(host, jax.ShapeDtypeStruct((), jnp.float32),
                        jnp.sum(x), ordered=True)
        return y + 1.0

    out = f(jnp.arange(4, dtype=jnp.float32))
    assert float(out) == 13.0 and seen == [6.0]
    assert out.devices() == {jax.devices()[0]}
