"""Model families (RBM, autoencoders, LSTM, convolution) — importing this
package registers their layer types in the layer registry."""

from deeplearning4j_tpu.models.pretrain import (  # noqa: F401
    RBM,
    AutoEncoder,
    RecursiveAutoEncoder,
    binomial_corruption,
)
from deeplearning4j_tpu.models.conv import ConvolutionDownSampleLayer  # noqa: F401
from deeplearning4j_tpu.models.lstm import LSTM  # noqa: F401
from deeplearning4j_tpu.models.transformer import (  # noqa: F401
    TransformerConfig,
    init_transformer_params,
    transformer_logits,
)
from deeplearning4j_tpu.models.moe_transformer import (  # noqa: F401
    MoEConfig,
    init_moe_params,
)
