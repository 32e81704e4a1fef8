"""Model zoo: symbol factories.

Reference: example/image-classification/symbols/*.py — the networks
behind every BASELINE.md number (resnet/alexnet/vgg/inception-bn/lenet).
Same architectures, composed from this framework's symbol API; on TPU
the whole network compiles to one XLA module per executor.
"""
from . import (lenet, mlp, resnet, alexnet, vgg, inception_bn, ssd,
               inception_v3, resnext, qwen3_next, deepseek_v3, afmoe,
               ouro)

_FACTORY = {
    'lenet': lenet.get_symbol,
    'mlp': mlp.get_symbol,
    'resnet': resnet.get_symbol,
    'alexnet': alexnet.get_symbol,
    'vgg': vgg.get_symbol,
    'inception-bn': inception_bn.get_symbol,
    'inception_bn': inception_bn.get_symbol,
    'inception-v3': inception_v3.get_symbol,
    'inception_v3': inception_v3.get_symbol,
    'resnext': resnext.get_symbol,
    'ssd': ssd.get_symbol_train,
    'qwen3_next': qwen3_next.get_symbol,
    'deepseek_v3': deepseek_v3.get_symbol,
    'afmoe': afmoe.get_symbol,
    'ouro': ouro.get_symbol,
}


def get_symbol(network, **kwargs):
    """Factory dispatch (the role of example/image-classification
    train scripts' `import symbols.<net>`)."""
    if network.startswith('resnet'):
        if network != 'resnet':
            kwargs.setdefault('num_layers', int(network[len('resnet'):]))
        return resnet.get_symbol(**kwargs)
    return _FACTORY[network](**kwargs)
