"""Model families (RBM, autoencoders, LSTM, convolution) — importing this
package registers their layer types in the layer registry. `model_of`
is the one place that knows which language-model module runs a
configuration."""

from deeplearning4j_tpu.models.pretrain import (  # noqa: F401
    RBM,
    AutoEncoder,
    RecursiveAutoEncoder,
    binomial_corruption,
)
from deeplearning4j_tpu.models.conv import ConvolutionDownSampleLayer  # noqa: F401
from deeplearning4j_tpu.models.lstm import LSTM  # noqa: F401
from deeplearning4j_tpu.models import (hybrid_transformer, moe_transformer,
                                       transformer)
from deeplearning4j_tpu.models.transformer import (  # noqa: F401
    TransformerConfig,
    init_transformer_params,
    transformer_logits,
)
from deeplearning4j_tpu.models.moe_transformer import (  # noqa: F401
    MoEConfig,
    init_moe_params,
)
from deeplearning4j_tpu.models.hybrid_transformer import (  # noqa: F401
    HybridConfig,
    init_hybrid_params,
)


def model_of(cfg):
    """The module that runs a language model's configuration. Each has
    `forward(params, tokens, positions, cfg, attend, valid=None) ->
    (hidden, cache states a layer, what its layers count)` over its one
    `block`, and `head(params, x, cfg)`; the caches (serving/) are these
    under their `attend` callbacks (`models/transformer.Attend`) and ask
    nothing else of a model."""
    for config_type, module in ((TransformerConfig, transformer),
                                (MoEConfig, moe_transformer),
                                (HybridConfig, hybrid_transformer)):
        if isinstance(cfg, config_type):
            return module
    raise TypeError(f"no language model runs a {type(cfg).__name__}")
