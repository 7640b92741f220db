"""The plain reference against the served path at a reduced size (CPU).

Both configurations' files, cut to 2 layers of width 128 (FFN 200, so
the served format pads it to 256) and a 255-token server: prompts of
100-180 tokens then decode with the LOP screen keeping 1 of 8 blocks.
The reference has to give the program's prefill logits and the greedy
tokens the scheduler served; the fp8 control, read through the same
comparison a run makes (``check.check``), has to fail the
configuration's limit.

At this width one int8 rounding flip (the program and the reference
round the same f32 values computed in another order) moves a logit by
about 1e-2, so agreement is to that, not to f32 rounding: the prefill
logits within LOGIT_TOL, at least 95% of the served tokens exactly the
reference's argmax, none more than ARGMAX_TOL below it.
"""

import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import traffic
from bench import weights as W
from bench.models import dense
from bench.reference import check, model

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
SEED = 2**35 + 11
MAX_LEN = 255
REQUESTS = ((150, 60), (100, 40), (180, 70))
MIX = {"loop": "closed", "clients": 3,
       "prompt_len": {"dist": "uniform", "lo": 100, "hi": 180},
       "max_tokens": {"dist": "uniform", "lo": 40, "hi": 70}}
LOGIT_TOL = 0.1
ARGMAX_TOL = 0.05


def tiny(name):
    conf = json.loads((CONFIGS / f"{name}.json").read_text())
    conf["arch"].update(n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
                        head_dim=32, d_ff=200, vocab=512, lop_block=32)
    conf["server"].update(slots=3, max_len=MAX_LEN)
    return conf


@pytest.fixture(scope="module", params=["bitnet-3b", "stablelm-1.6b"])
def served(request):
    from repro.serving.api import GenerateRequest, PooledEngine
    from repro.serving.metrics import MetricsRegistry
    from repro.serving.scheduler import Scheduler

    conf = tiny(request.param)
    cfg = dense.model_config(conf)
    qp = dense.served_params(conf["arch"], SEED)
    eng = PooledEngine(cfg, qp, max_len=MAX_LEN)
    sched = Scheduler(cfg, qp, n_slots=3, max_len=MAX_LEN, engine=eng,
                      metrics=MetricsRegistry())
    prompts = [np.asarray(traffic.prompt(SEED, 0, i, n, 512), np.int32)
               for i, (n, _) in enumerate(REQUESTS)]
    for i, (p, (_, m)) in enumerate(zip(prompts, REQUESTS)):
        sched.submit(GenerateRequest(rid=i, prompt=p, max_new_tokens=m))
    done = {r.rid: r.tokens for r in sched.run_to_completion()}
    return conf, eng, prompts, [done[i] for i in range(len(REQUESTS))]


def as_run(prompts, toks):
    """The served requests as the load generator records them."""
    recs = [{"stream": 0, "index": i, "prompt_len": len(p), "due": 0.5,
             "tokens": [int(x) for x in t], "max_tokens": len(t),
             "status": 200, "done": True} for i, (p, t) in
            enumerate(zip(prompts, toks))]
    return {"t_start": 0.0, "t_end": 1.0, "records": recs}


def ref_logits(conf, prompt, toks):
    arch = conf["arch"]
    cap = model.capacity_of(arch, MAX_LEN)
    seq = list(prompt) + list(toks[:-1])
    return dense.logits(arch, SEED, seq, len(prompt), length=cap,
                        k_keep=model.k_keep_blocks(arch, cap),
                        n_rows=80)[:len(toks)]


def test_weights_are_the_same_numbers_on_both_sides(served):
    conf, eng, _, _ = served
    arch = conf["arch"]
    key = W.layer_keys(W.seed_data(SEED), arch["n_layers"])[1]
    plain = dense.plain_layer_jit(W.frozen(arch), key)
    packed = eng.qp["layers"]["ffn"]["down_packed"][1]
    from repro.core.ternary import unpack_ternary
    down = np.asarray(unpack_ternary(packed, packed.shape[0] * 4))
    assert np.array_equal(down[:200], np.asarray(plain["wd"]))
    assert not down[200:].any()


def test_prefill_logits_match(served):
    conf, eng, prompts, toks = served
    p = prompts[0]
    got, _ = eng.prefill(p[None], len(p), {})
    want = ref_logits(conf, p, toks[0])[0]
    np.testing.assert_allclose(np.asarray(got)[0], want, rtol=0,
                               atol=LOGIT_TOL)


def test_served_tokens_are_the_reference_argmax(served):
    conf, _, prompts, toks = served
    gaps = np.concatenate([check.token_gaps(ref_logits(conf, p, t),
                                            np.asarray(t))
                           for p, t in zip(prompts, toks)])
    assert gaps.max() <= ARGMAX_TOL
    assert (gaps == 0).mean() >= 0.95
    verdict = check.check(conf, MIX, as_run(prompts, toks), SEED,
                          model=dense, sample_tokens=10**6)
    assert verdict["ok"] is True
    assert verdict["read"]["max_logit_gap"] == pytest.approx(gaps.max())


def test_fp8_control_fails_the_limit(served):
    conf, _, prompts, toks = served
    verdict = check.check(conf, MIX, as_run(prompts, toks), SEED,
                          model=dense, sample_tokens=10**6, control=True)
    assert verdict["ok"] is False
    gap = verdict["compared"]["max_logit_gap"]
    assert gap["value"] > 3 * ARGMAX_TOL and gap["value"] > gap["limit"]


def test_fp8_rounds_to_three_mantissa_bits():
    x = jnp.asarray([448.0, 1.0, 1.0625, 1.1875, 0.3, -2.0**-8, 0.0])
    got = model.fp8(x, None).tolist()
    assert got == [448.0, 1.0, 1.0, 1.25, 0.3125, -2.0**-8, 0.0]
    # below 2^-6 the step is 2^-9 (subnormals): 1.5 steps round to 2
    assert model.fp8(jnp.asarray([448.0, 3 * 2.0**-10]), None).tolist() \
        == [448.0, 2.0**-8]
    # one scale per row maps each row's largest |x| to 448
    # (0.3 · 448 = 134.4, on the grid 128, 144, ...: 128)
    np.testing.assert_allclose(
        model.fp8(jnp.asarray([[1.0, 0.3], [4.0, 1.2]])),
        [[1.0, 128 / 448], [4.0, 4 * 128 / 448]], rtol=1e-6)


def test_selector_keeps_k_blocks_in_index_order_on_ties():
    s = jnp.asarray([[5.0, 5.0, 5.0, -jnp.inf, 1.0]])
    assert model.select_blocks(s, 2).tolist() == [[True, True, False,
                                                   False, False]]
    s = jnp.asarray([[0.0, 9.0, 3.0, 8.0]])
    assert model.select_blocks(s, 2).tolist() == [[False, True, False,
                                                   True]]
    s = jnp.asarray([[2.0, -jnp.inf, -jnp.inf]])
    assert model.select_blocks(s, 2).tolist() == [[True, False, False]]
