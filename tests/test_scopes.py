"""The program's profiler names: the ``jax.named_scope``s its compiled
round-blocks carry in their ops' metadata, and the host spans it opens
around a block's dispatch and the block-edge work. The benchmark's by-scope
reduction (``bench/scopes.py``) reads these names, so a rename fails here
first. The vmap round-block compiled for a TPU v5e is checked in
``tests/test_tpu_compile.py``."""
import re

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import DPConfig, ProxyFLConfig
from repro.configs.registry import proxy_of, smoke_variant
from repro.core.accountant import PrivacyAccountant
from repro.core.engine import dml_engine
from repro.core.protocol import ModelSpec
from repro.data.synthetic import make_classification_data, make_lm_data
from repro.launch import train
from repro.nn.vision import get_vision_model

SEQ = 16


def _lm(backend, K, **fl_kw):
    cfg = smoke_variant(get_config("qwen1.5-4b"))
    proxy = smoke_variant(proxy_of(cfg))
    fl = ProxyFLConfig(n_clients=K, local_steps=1, batch_size=2,
                       dp=DPConfig(enabled=True), **fl_kw)
    eng = train.make_engine(cfg, proxy, fl, backend)
    key = jax.random.PRNGKey(0)
    data = [make_lm_data(jax.random.fold_in(key, k), 4 * (SEQ + 1), 64,
                         domain=k).reshape(4, SEQ + 1) for k in range(K)]
    return eng, cfg, data


def block_hlo(eng, data, T=2):
    """The optimized HLO text of ``eng``'s round-block program: one block
    builds it, the next is called through a spy that keeps its argument
    shapes."""
    key = jax.random.PRNGKey(0)
    state, _ = eng.run_rounds(eng.init_states(key), data, 0, T, key)
    (rkey, fn), = eng._rounds.items()
    seen = {}

    def spy(*args):
        seen["args"] = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
        return fn(*args)

    eng._rounds[rkey] = spy
    eng.run_rounds(state, data, T, T, key)
    return fn.lower(*seen["args"]).compile().as_text()


def op_names(hlo: str):
    return re.findall(r'op_name="([^"]*)"', hlo)


def unscoped_matmuls(hlo: str, scopes=("fl.local", "fl.exchange")):
    """Dots and convolutions whose metadata names none of ``scopes``; ops
    the compiler made with no metadata at all are not the program's."""
    out = []
    for line in hlo.splitlines():
        if re.search(r"= \S+ (dot|convolution)\(", line) and \
                "op_name=" in line and not any(s in line for s in scopes):
            out.append(line.strip()[:160])
    return out


@pytest.mark.parametrize("backend,K,fl_kw", [
    ("async", 2, {"staleness": 1}),
    ("hier", 4, {"n_shards": 2}),
    ("hier", 4, {"n_shards": 2, "staleness": 1}),
], ids=["async", "hier", "hier-stale"])
def test_stale_and_hier_exchanges_carry_the_exchange_scope(backend, K, fl_kw):
    eng, _, data = _lm(backend, K, **fl_kw)
    hlo = block_hlo(eng, data)
    names = op_names(hlo)
    assert any("fl.exchange" in n for n in names)
    assert any("fl.local" in n for n in names)
    assert unscoped_matmuls(hlo) == []


def test_protocol_step_scopes_in_the_classifier_round_block():
    """The paper-scale DML step (``repro.core.protocol.dml_step_fn``, run
    by ``dml_engine``) names its private and proxy gradients and its
    optimizer updates as the LLM step does."""
    vm = get_vision_model("mlp")
    spec = ModelSpec("mlp", lambda k: vm.init(k, (8, 8, 1), 4), vm.apply)
    cfg = ProxyFLConfig(n_clients=2, local_steps=1, batch_size=4,
                        dp=DPConfig(enabled=True))
    eng = dml_engine((spec, spec), spec, cfg)
    x, y = make_classification_data(jax.random.PRNGKey(1), 64, (8, 8, 1), 4)
    hlo = block_hlo(eng, [(x[:32], y[:32]), (x[32:], y[32:])])
    names = op_names(hlo)
    for scope in ("fl.local", "fl.exchange", "fl.private", "fl.proxy",
                  "fl.adam", "fl.loss"):
        assert any(scope in n for n in names), scope
    assert unscoped_matmuls(hlo) == []


def test_block_dispatch_and_edge_spans_in_a_profiled_run(tmp_path):
    """``run_rounds`` opens ``fl.dispatch`` around the block's enqueue and
    closes it before the block is waited on; ``evaluate_ppl`` and the
    accountant's ``epsilon`` open the block edge's spans."""
    eng, cfg, data = _lm("vmap", 2)
    acc = PrivacyAccountant(1.0, 0.5, 1e-5)
    eng.attach_accountants([acc, acc])
    key = jax.random.PRNGKey(0)
    state, _ = eng.run_rounds(eng.init_states(key), data, 0, 2, key)
    test = data[0][:2]
    train.evaluate_ppl(eng.client_params(state, 0, "private"), cfg, test)
    with jax.profiler.trace(str(tmp_path)):
        state, metrics = eng.run_rounds(state, data, 2, 2, key)
        ppl = train.evaluate_ppl(eng.client_params(state, 0, "private"),
                                 cfg, test)
        eps = acc.epsilon()
    assert np.isfinite(ppl) and eps > 0
    assert metrics["private_loss"].shape == (2, 2)
    spans = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for pb in tmp_path.glob("**/*.xplane.pb")
                   for plane in jax.profiler.ProfileData.from_file(
                       str(pb)).planes if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith("fl."))
    names = [n for _, _, n in spans]
    assert names == ["fl.dispatch", "fl.edge.eval", "fl.edge.epsilon"]
    (d0, d1, _), (e0, e1, _), (p0, _, _) = spans
    assert d0 < d1 <= e0 < e1 <= p0
