"""Model zoo: every model family the reference ships as examples.

Reference coverage (SURVEY.md §2.6 "Examples" row):
- CNN family   (examples/cnn/models/): MLP, LogReg, 3-layer CNN, LeNet,
  AlexNet, VGG-16/19, ResNet-18/34/50/101/152, RNN, LSTM
- NLP          (examples/nlp/): BERT (hetu_bert.py), MT Transformer
  (hetu_transformer.py)
- CTR          (examples/ctr/models/): WDL (adult/criteo), DCN, DeepFM, DC
- Rec          (examples/rec/hetu_ncf.py): NCF
- MoE          (examples/moe/): MoE MLP classifiers with the gate family

Each CNN-family builder keeps the reference's functional signature
``model(x, y_) -> (loss, y)`` so reference training scripts map 1:1;
BERT/Transformer are classes (the reference's BERT is class-based too).
"""

from .cnn import (
    mlp, logreg, cnn_3_layers, lenet, alexnet, vgg, vgg16, vgg19,
    resnet, resnet18, resnet34, resnet50, resnet101, resnet152,
    rnn, lstm, fc,
)
from .bert import (
    BertConfig, BertModel, BertForPreTraining,
    BertForSequenceClassification, BertForMaskedLM,
    BertForQuestionAnswering,
)
from .bert_moe import (
    BertMoEConfig, BertMoEModel, BertMoEForPreTraining,
)
from .transformer import TransformerConfig, Transformer, transformer_mt
from .gpt import GPTConfig, GPTModel, GPTForCausalLM
from .ctr import (
    wdl_adult, wdl_criteo, dcn_criteo, deepfm_criteo, dc_criteo,
)
from .ncf import neural_mf
from .moe_models import moe_mlp, moe_transformer_block
from .moe_decode import (
    MoEDecodeConfig, MoESpec, moe_spec_of, moe_capacity, moe_ffn,
    moe_ffn_ep_reference, ep_shard_params, init_moe_params,
    convert_dense_to_moe, resolve_moe_capacity, resolve_moe_quant,
    LatentMoEConfig, RoutedSpec, routed_ffn, init_latent_moe_params,
)
from .parallel_moe import ParallelMoEConfig, init_parallel_moe_params
from .kda_latent import KDALatentConfig, init_kda_latent_params
from .kda_gqa import KDAGQAConfig, init_kda_gqa_params
