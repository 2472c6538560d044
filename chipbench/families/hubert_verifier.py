"""The HuBERT X-Large verifier family: its seeded weights, its engine, its operations.

A configuration file names its family (``"family": "hubert_verifier"``) and
the harness finds this module by that name (``catalog.family``).  It
defines the three functions of a family module (see ``shield8_cnn.py``).

The configuration's ``model`` holds the published widths under the names of
the ``facebook/hubert-xlarge-ll60k`` config (``conv_dim``, ``conv_kernel``,
``conv_stride``, ``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``intermediate_size``, ``num_conv_pos_embeddings``,
``num_conv_pos_embedding_groups``, ``classifier_proj_size``, ``num_labels``,
``layer_norm_eps``) and the window it scores (``input_len``).  The float
weights are laid out as ``repro.models.hubert`` documents: ``x @ w``
linears, ``(kernel, in, out)`` convs, the positional conv as weight norm's
``pos_v`` and ``pos_g``, one dict per encoder layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import catalog
from chipbench.weights import key

#: standard deviation of linear weights (HF ``initializer_range``), and of
#: biases and LayerNorm offsets (assumed: HF zeroes them)
STD = 0.02
#: relative spread of the positional conv's weight-norm gains around the
#: norm of ``pos_v`` (assumed: HF starts them equal, so the fold is idle)
POS_G_SPREAD = 0.1


def weights(model: dict, seed: int) -> dict:
    """Float32 weights on the default device, in one jitted call: linears
    N(0, 0.02^2) and convs kaiming-normal, as HF's ``_init_weights``;
    biases and LayerNorm terms drawn small and non-zero, so that every
    epilogue carries real values."""
    d, f = model["hidden_size"], model["intermediate_size"]
    n, k_pos, groups = (model["num_hidden_layers"], model["num_conv_pos_embeddings"],
                        model["num_conv_pos_embedding_groups"])
    proj, labels = model["classifier_proj_size"], model["num_labels"]

    @jax.jit
    def init(k):
        keys = iter(jax.random.split(k, 64))

        def normal(shape, std, mean=0.0):
            return mean + std * jax.random.normal(next(keys), shape, jnp.float32)

        def norm(lead, c):
            return {"g": normal(lead + (c,), STD, 1.0), "b": normal(lead + (c,), STD)}

        conv, c_in = [], 1
        for c, kw in zip(model["conv_dim"], model["conv_kernel"]):
            ln = norm((), c)
            conv.append({"w": normal((kw, c_in, c), (2.0 / (kw * c_in)) ** 0.5),
                         "b": normal((c,), STD), "ln_g": ln["g"], "ln_b": ln["b"]})
            c_in = c
        feat_ln = norm((), c_in)
        pos_v = normal((k_pos, d // groups, d), (2.0 / (k_pos * d // groups)) ** 0.5)
        pos_g = jnp.sqrt(jnp.sum(pos_v * pos_v, axis=(1, 2))) * normal((k_pos,), POS_G_SPREAD, 1.0)
        # each kind of layer weight drawn once for all layers, then split
        ln1, ln2 = norm((n,), d), norm((n,), d)
        stacked = {"ln1_g": ln1["g"], "ln1_b": ln1["b"], "ln2_g": ln2["g"], "ln2_b": ln2["b"]}
        for name, (k_in, k_out) in {"q": (d, d), "k": (d, d), "v": (d, d), "o": (d, d),
                                    "ff1": (d, f), "ff2": (f, d)}.items():
            stacked[f"{name}_w"] = normal((n, k_in, k_out), STD)
            stacked[f"{name}_b"] = normal((n, k_out), STD)
        final_ln = norm((), d)
        return {
            "conv": conv, "feat_ln_g": feat_ln["g"], "feat_ln_b": feat_ln["b"],
            "feat_w": normal((c_in, d), STD), "feat_b": normal((d,), STD),
            "pos_v": pos_v, "pos_g": pos_g, "pos_b": normal((d,), STD),
            "layers": [{name: v[i] for name, v in stacked.items()} for i in range(n)],
            "final_ln_g": final_ln["g"], "final_ln_b": final_ln["b"],
            "proj_w": normal((d, proj), STD), "proj_b": normal((proj,), STD),
            "cls_w": normal((proj, labels), STD), "cls_b": normal((labels,), STD),
        }

    return init(key(seed))


def engine(cell, params):
    """The program under test, through its public engine constructor."""
    from repro.models.hubert import HubertConfig
    from repro.serving.engine import MonitorEngine

    cfgj = cell.config
    cfg = HubertConfig(**{k: tuple(v) if isinstance(v, list) else v
                          for k, v in cfgj["model"].items()})
    eng = cfgj["engine"]
    return MonitorEngine(
        params, cfg,
        n_streams=cell.traffic["streams"],
        feature_kind=cfgj["feature_kind"],
        on_device_features=True,
        batch_slots=eng["batch_slots_per_chip"] * cell.chips,
        precision=cfgj["bake"]["mode"],
        capacity_windows=eng["capacity_windows"],
        **eng["tracker"],
    )


def layers(config: dict) -> list[tuple[str, int, str]]:
    """``[(layer, operations per window, stated precision)]``, one entry per
    named scope of the served forward that does work; the input
    normalisation is not counted."""
    conv = catalog.kernel("strided_conv")
    matmul = catalog.kernel("matmul")
    m = config["model"]
    prec = config["stated_precision"]
    d, f, n = m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"]
    heads, k_pos = m["num_attention_heads"], m["num_conv_pos_embeddings"]
    wave, t, c_in = 0, m["input_len"], 1
    for c, k, s in zip(m["conv_dim"], m["conv_kernel"], m["conv_stride"]):
        t = conv.out_len(t, k, s)
        wave += conv.ops(1, t, k, c_in, c)
        c_in = c
    dh = d // heads
    attn = 4 * matmul.ops(t, d, d) + heads * (matmul.ops(t, dh, t) + matmul.ops(t, t, dh))
    ops = {
        "waveform": wave,
        "featproj": matmul.ops(t, c_in, d),
        "posconv": conv.ops(1, t, k_pos, d, d, m["num_conv_pos_embedding_groups"]),
        "attn": n * attn,
        "ffn": n * (matmul.ops(t, d, f) + matmul.ops(t, f, d)),
        "head": matmul.ops(t, d, m["classifier_proj_size"])
        + matmul.ops(1, m["classifier_proj_size"], m["num_labels"]),
    }
    return [(name, v, prec[name]) for name, v in ops.items()]
