"""The benchmark's own operation counts against hand counts, and its
parameter layout against the program's models."""
import jax
import pytest

import benchtiny  # noqa: F401  (puts the repository on the path)
from bench import counts, reference_lm
from bench.family_lm import model_dicts


def test_lm_flops_per_token_by_hand():
    # one Phi-3-mini layer at seq 512, 8016-way head:
    # q, k, v, o: 4 x 3072^2; SwiGLU: 3 x 3072 x 8192 (2 FLOPs a MAC)
    mm = 2 * (4 * 3072 * 3072 + 3 * 3072 * 8192)
    attn = 2 * 2 * 32 * 96 * (512 + 1) / 2
    head = 2 * 3072 * 8016
    assert counts.lm_forward_flops_per_token(
        3072, 1, 32, 32, 96, 8192, 8016, 512) == pytest.approx(mm + attn + head)
    assert 0.278e9 < mm + attn + head < 0.280e9


def test_lm_round_flops_match_the_cell():
    import json
    cfg = json.loads((benchtiny.ROOT / "bench" / "configs"
                      / "phi3-mini-fed4.json").read_text())
    m = model_dicts(cfg)
    per_tok = counts.lm_model_flops(m["private"], 512) + counts.lm_model_flops(
        m["proxy"], 512)
    assert counts.lm_round_flops(m["private"], m["proxy"], 4, 4, 4, 512) == \
        pytest.approx(3 * 4 * 4 * 4 * 512 * per_tok)


def test_lm_layout_matches_the_program():
    """The reference reads the program's parameter tree leaf for leaf."""
    import json
    from bench import weights
    from bench.family_lm import program_configs
    from repro.nn.model import init_model
    cfg = json.loads((benchtiny.DATA / "tiny" / "tiny-lm.json").read_text())
    m = model_dicts(cfg)
    private, proxy = program_configs(m)
    for role, pc in (("private", private), ("proxy", proxy)):
        got = jax.eval_shape(lambda k: init_model(k, pc), jax.random.PRNGKey(0))
        assert weights.same_layout(got, reference_lm.layout(m[role]))
