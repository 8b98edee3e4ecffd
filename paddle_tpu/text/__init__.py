"""paddle_tpu.text — NLP model zoo (ref: python/paddle/text/ + the
PaddleNLP-era ERNIE family targeted by BASELINE.json), a decoder-only
expert family (`deepseek_v3`), a hybrid convolution/attention expert family
(`lfm2_moe`), and the trainer that takes any of them as a `PretrainModel`
(`HybridPretrainer`)."""
from .datasets import (Conll05st, Imdb, Imikolov, Movielens,
                       MovieReviews, UCIHousing, WMT14, WMT16)
from .ernie import (
    BertConfig,
    BertForPretraining,
    BertModel,
    ErnieConfig,
    ErnieForPretraining,
    ErnieForSequenceClassification,
    ErnieModel,
    ErniePretrainingCriterion,
)
from .deepseek_v3 import DeepseekV3Config
from .lfm2_moe import Lfm2MoeConfig
from .pretrainer import HybridPretrainer, PretrainModel, ernie_pretrain_model
