"""Model factory registry: config factory strings -> Model builders.

Mirrors the reference's string-addressed factories
(murmura/utils/factories.py:45-61: ``examples.leaf.*`` / ``examples.wearables.*``
prefixes) plus native ids for the new framework's own models.
"""

from typing import Any, Dict

from murmura_tpu.models.cnn import FEMNIST_VARIANTS, make_celeba_cnn, make_femnist_cnn
from murmura_tpu.models.core import Model
from murmura_tpu.models.decoder import make_deepseek_v3
from murmura_tpu.models.lstm import make_char_lstm
from murmura_tpu.models.mlp import make_mlp, make_wearable_mlp
from murmura_tpu.models.zaya import make_zaya1

# Wearable dataset default dims (reference: murmura/examples/wearables/models.py:195-300:
# UCI HAR 561/(256,128); PAMAP2 4000 = 100-window x 40 feats /(512,256,128);
# PPG-DaLiA 192 = 32-window x 6 feats /(256,128,64))
_WEARABLE_DEFAULTS = {
    "uci_har": {"input_dim": 561, "hidden_dims": (256, 128), "num_classes": 6},
    "pamap2": {"input_dim": 4000, "hidden_dims": (512, 256, 128), "num_classes": 12},
    "ppg_dalia": {"input_dim": 192, "hidden_dims": (256, 128, 64), "num_classes": 7},
}


def build_model(factory: str, params: Dict[str, Any]) -> Model:
    """Resolve a config ``model.factory`` string to a Model.

    Accepted ids:
    - ``mlp`` — generic softmax MLP (params: input_dim, hidden_dims,
      num_classes, dropout, evidential).
    - ``examples.leaf.LEAFFEMNISTModel`` / ``leaf.femnist[.variant]`` —
      FEMNIST CNN family (variant in tiny/small/baseline/large/xlarge).
    - ``examples.leaf.LEAFCelebAModel`` / ``leaf.celeba`` — CelebA CNN.
    - ``leaf.shakespeare`` — char-LSTM.
    - ``decoder.deepseek_v3`` — a DeepSeek-V3-family decoder (latent
      attention, routed and shared experts, next-token output); params
      are the published ``config.json``'s own keys plus ``seq_len``,
      ``ep_size``/``ep_rank`` (the experts held here) and the training
      rule's ``aux_loss_alpha``/``bias_update_speed`` (models/decoder.py).
    - ``decoder.zaya1`` — a ZAYA1 decoder (compressed convolutional
      attention with grouped query heads, an MLP router over a state
      averaged across depth choosing one expert, learned residual scales,
      a tied head); params are the published ``config.json``'s own keys
      (``rope_theta`` as its ``rope_parameters.hybrid`` gives it) plus
      ``seq_len``, ``ep_size``/``ep_rank`` and ``bias_update_speed``
      (models/zaya.py).
    - ``examples.wearables.<uci_har|pamap2|ppg_dalia>`` /
      ``wearables.<...>`` — evidential wearable MLPs.
    """
    params = dict(params or {})
    f = factory.strip()
    compute_dtype = params.pop("compute_dtype", None)

    if f == "mlp":
        evidential = bool(params.pop("evidential", False))
        return make_mlp(
            input_dim=int(params.pop("input_dim", 32)),
            hidden_dims=tuple(params.pop("hidden_dims", (64, 32))),
            num_classes=int(params.pop("num_classes", 10)),
            dropout_rate=float(params.pop("dropout", 0.0)),
            evidential=evidential,
            compute_dtype=compute_dtype,
        )

    if f == "decoder.deepseek_v3":
        return make_deepseek_v3(**params, compute_dtype=compute_dtype)

    if f == "decoder.zaya1":
        return make_zaya1(**params, compute_dtype=compute_dtype)

    lowered = f.lower()
    if "femnist" in lowered:
        variant = params.pop("variant", None)
        if variant is None:
            tail = lowered.rsplit(".", 1)[-1]
            variant = tail if tail in FEMNIST_VARIANTS else "baseline"
        return make_femnist_cnn(
            num_classes=int(params.pop("num_classes", 62)), variant=variant,
            compute_dtype=compute_dtype,
        )

    if "celeba" in lowered:
        return make_celeba_cnn(
            num_classes=int(params.pop("num_classes", 2)),
            compute_dtype=compute_dtype,
        )

    if "shakespeare" in lowered:
        return make_char_lstm(
            vocab_size=int(params.pop("vocab_size", 81)),
            embed_dim=int(params.pop("embed_dim", 8)),
            hidden=int(params.pop("hidden", 256)),
            num_layers=int(params.pop("num_layers", 2)),
            seq_len=int(params.pop("seq_len", 80)),
            compute_dtype=compute_dtype,
        )

    for prefix in ("examples.wearables.", "wearables."):
        if f.startswith(prefix):
            kind = f[len(prefix):]
            defaults = dict(_WEARABLE_DEFAULTS.get(kind, _WEARABLE_DEFAULTS["uci_har"]))
            defaults.update(params)
            return make_wearable_mlp(
                input_dim=int(defaults["input_dim"]),
                hidden_dims=tuple(defaults["hidden_dims"]),
                num_classes=int(defaults["num_classes"]),
                dropout=float(defaults.get("dropout", 0.3)),
                name=f"wearables.{kind}",
                compute_dtype=compute_dtype,
            )

    raise ValueError(f"Unknown model factory: {factory!r}")
