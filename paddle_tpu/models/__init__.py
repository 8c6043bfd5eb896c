"""Model zoo covering the BASELINE workload ladder:
MNIST LeNet, ResNet-50, BERT-base, ERNIE-large, Transformer-big —
plus word2vec and the seq2seq machine-translation book model.
"""

from . import bert, lenet  # noqa: F401
from . import resnet  # noqa: F401
from . import transformer  # noqa: F401
from . import seq2seq  # noqa: F401
from . import word2vec  # noqa: F401
