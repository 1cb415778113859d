"""LM federations: the program's ``repro.launch.train`` path (private decoder
plus the system's ``proxy_of`` proxy, DP-SGD on the proxy, PushSum), built
from a configuration of the ``lm`` family and an LM traffic file."""
from __future__ import annotations

from typing import Dict

import jax

from . import counts, reference_lm, traffic as gen
from .system import Federation

#: proxy keys the configuration file gives; the rest follow the private model
PROXY_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "num_hidden_layers",
              "tie_word_embeddings")


def model_dicts(config: Dict) -> Dict[str, Dict]:
    """The private and proxy decoders in the configuration's keys."""
    private = dict(config["config"])
    proxy = dict(private)
    proxy.update({k: config["proxy"][k] for k in PROXY_KEYS})
    return {"private": private, "proxy": proxy}


def program_configs(models: Dict[str, Dict]):
    """The program's ``ModelConfig`` of the private model and its proxy by
    the system's own ``proxy_of``; refuses a proxy that is not the one the
    configuration states."""
    from repro.configs.base import LayerSpec, ModelConfig
    from repro.configs.registry import proxy_of

    m = models["private"]
    cfg = ModelConfig(
        name="bench-private", arch_type="dense", modality="text",
        vocab_size=m["vocab_size"], d_model=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"],
        pattern=(LayerSpec(kind="attn", ffn="dense"),),
        rope_theta=m["rope_theta"], norm_eps=m["rms_norm_eps"],
        tie_embeddings=m["tie_word_embeddings"], dtype=m["torch_dtype"])
    x = models["proxy"]
    proxy = proxy_of(cfg, n_layers=x["num_hidden_layers"],
                     d_model=x["hidden_size"])
    got = (proxy.n_heads, proxy.n_kv_heads, proxy.resolved_head_dim,
           proxy.d_ff, proxy.tie_embeddings)
    want = (x["num_attention_heads"], x["num_key_value_heads"],
            x["head_dim"], x["intermediate_size"], x["tie_word_embeddings"])
    if got != want:
        raise ValueError(f"proxy_of gives {got}, the configuration {want}")
    return cfg, proxy


class LMFederation(Federation):
    reference = reference_lm

    def __init__(self, config: Dict, traffic: Dict, seed: int, devices):
        super().__init__(seed)
        from repro.configs.base import DPConfig, ProxyFLConfig
        from repro.launch.train import make_engine

        self.config, self.traffic = config, traffic
        self.fed = dict(config["federation"])
        self.models = model_dicts(config)
        self.cfg, self.proxy = program_configs(self.models)
        K = self.fed["clients"]
        self.rounds_per_block = traffic["rounds_per_block"]
        self.local_steps = traffic["local_steps"]
        fl = ProxyFLConfig(
            alpha=self.fed["alpha"], beta=self.fed["beta"], n_clients=K,
            rounds=1, local_steps=self.local_steps, lr=self.fed["lr"],
            weight_decay=self.fed["weight_decay"],
            batch_size=traffic["batch"], topology=traffic["topology"],
            use_pallas=traffic["use_pallas"],
            dp=DPConfig(enabled=traffic["dp"],
                        clip_norm=self.fed["dp_clip"],
                        noise_multiplier=self.fed["dp_sigma"],
                        delta=self.fed["dp_delta"]))
        backend = self.fed["backend"]
        if backend == "shard_map":
            self.mesh = jax.make_mesh((K,), ("clients",),
                                      devices=list(devices)[:K])
        self.engine = make_engine(self.cfg, self.proxy, fl, backend,
                                  self.mesh)
        self.mesh = self.engine.mesh   # the engine re-types its mesh

    def setup(self) -> None:
        """Data, accountants and state, all made from the seed."""
        from repro.core.accountant import PrivacyAccountant

        traffic, K = self.traffic, self.fed["clients"]
        self.data, self.test = self.make_data()
        if traffic["dp"]:
            q = min(1.0, traffic["batch"] / traffic["corpus_seqs"])
            self.engine.attach_accountants([
                PrivacyAccountant(self.fed["dp_sigma"], q,
                                  self.fed["dp_delta"]) for _ in range(K)])
        self.make_state()

    def make_data(self):
        tr = self.traffic
        return gen.lm_federation(
            self.keys["data"], self.fed["clients"], tr["corpus_seqs"],
            tr["seq"], self.models["private"]["vocab_size"], tr["test_seqs"])

    def ref_data(self):
        return self.make_data()[0]

    def data_shapes(self):
        """``[K, n, seq + 1]`` shape of the stacked per-client corpora."""
        tr = self.traffic
        return (((self.fed["clients"], tr["corpus_seqs"], tr["seq"] + 1),
                 "int32"),)

    def edge(self, metrics) -> Dict[str, float]:
        """As ``repro.launch.train.run`` at a block edge: client 0's test
        perplexity and the largest epsilon."""
        from repro.launch.train import evaluate_ppl

        ppl = evaluate_ppl(self.engine.client_params(self.state, 0, "private"),
                           self.cfg, self.test, batch=self.traffic[
                               "eval_batch"])
        eps = max((a.epsilon() for a in self.engine.accountants
                   if a is not None), default=float("nan"))
        return {"ppl": ppl, "eps": eps}

    def round_flops(self) -> float:
        tr = self.traffic
        return counts.lm_round_flops(
            self.models["private"], self.models["proxy"],
            self.fed["clients"], tr["local_steps"], tr["batch"], tr["seq"])


def build(config: Dict, traffic: Dict, seed: int, devices) -> LMFederation:
    return LMFederation(config, traffic, seed, devices)

