"""The plain float32 reference against the served engine, at the smoke
size on the CPU: prefill and decode through the engine agree with it,
and a reference with a wrong layer, or computed in float8, does not."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import harness, model, smoke  # noqa: E402

SEED = 2**31 + 11


def test_layer_weights_match_the_one_call_init():
    shape = model.Shape(3, 16, 2, 1, 24, 40, 10000.0, 1e-5)
    p = model.make_params(SEED, shape, jnp.bfloat16)
    key = model.base_key(SEED)
    for i in range(shape.n_layers):
        w = model.layer_weights(key, shape, i)
        layers = p["backbone"]["layers"]
        got = {"ln1": layers["ln1"]["scale"][i], "wq": layers["attn"]["wq"][i],
               "wd": layers["mlp"]["wd"][i], "ln2": layers["ln2"]["scale"][i]}
        for k, v in got.items():
            np.testing.assert_array_equal(np.asarray(v), np.asarray(w[k]))
    top = model.top_weights(key, shape)
    np.testing.assert_array_equal(np.asarray(p["head"]["table"]),
                                  np.asarray(top["head"]))


def _limit():
    return smoke.config("phi3-mini-3.8b")["checks"]["gap_max"]


@pytest.fixture(scope="module")
def served():
    """Greedy outputs of the served engine (mixed prefill megaticks, then
    pure decode megaticks over the paged pool) for a few prompts."""
    from repro.serving.engine import Request

    conf = smoke.config("phi3-mini-3.8b")
    engine, shape = harness.build(conf, SEED, jax.devices()[:1])
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(1, shape.vocab, n)]
               for n in (19, 33, 24)]
    for i, p in enumerate(prompts):
        engine.submit(Request(rid=i, prompt=p, max_new_tokens=12))
    done = sorted(engine.run(), key=lambda r: r.rid)
    assert engine.mixed_dispatch_count and engine.decode_dispatch_count
    return shape, prompts, [r.out_tokens for r in done]


def test_served_tokens_agree_with_the_reference(served):
    shape, prompts, outputs = served
    ref = model.Reference(shape, SEED)
    gaps, _ = model.served_gaps(ref, prompts, outputs)
    assert len(gaps) == 36
    assert gaps.max() <= _limit()


class _WrongLayer(model.Reference):
    """The reference with every layer's weights taken from the next
    layer: the control that a layer mix-up must fail."""

    def __init__(self, shape, seed):
        super().__init__(shape, seed)
        inner = self._layer
        self._layer = lambda x, pos, seg, i, key: inner(
            x, pos, seg, (i + 1) % shape.n_layers, key)


def test_a_wrong_layer_and_the_float8_control_fail(served):
    shape, prompts, outputs = served
    ref = model.Reference(shape, SEED)
    sound, _ = model.served_gaps(ref, prompts, outputs)
    wrong, _ = model.served_gaps(_WrongLayer(shape, SEED), prompts,
                                 outputs)
    assert wrong.max() > _limit()
    ctl = model.Reference(shape, SEED, fp8=True)
    _, fp8 = model.served_gaps(ref, prompts, outputs, control=ctl)
    # float8 puts other tokens first far more often, and further below
    # the reference's best, than the served bfloat16 path does: past the
    # limit that the served path keeps
    assert sound.max() <= _limit() < fp8.max()
    assert fp8.max() > 5 * sound.max()
    assert (fp8 > 0).sum() > 3 * max((sound > 0).sum(), 1)
