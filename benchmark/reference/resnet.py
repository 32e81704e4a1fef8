"""ResNet for ImageNet, pre-activation bottleneck units, float32.

He et al., arXiv:1512.03385 Table 1 for the depths and widths and
arXiv:1603.05027 for the unit; parameter names as in the reference
framework's example/image-classification/symbols/resnet.py.
"""
UNITS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
FILTERS = (64, 256, 512, 1024, 2048)
EPS = 2e-5


def _unit(net, x, num_filter, stride, dim_match, name):
    act1 = net.relu(net.batchnorm(name + '_bn1', x, EPS))
    y = net.conv(name + '_conv1', act1, num_filter // 4, (1, 1))
    y = net.relu(net.batchnorm(name + '_bn2', y, EPS))
    y = net.conv(name + '_conv2', y, num_filter // 4, (3, 3), stride, (1, 1))
    y = net.relu(net.batchnorm(name + '_bn3', y, EPS))
    y = net.conv(name + '_conv3', y, num_filter, (1, 1))
    if dim_match:
        return y + x
    return y + net.conv(name + '_sc', act1, num_filter, (1, 1), stride)


def forward(net, x, num_classes, num_layers=None, units=None,
            filters=FILTERS):
    """Logits.  `units` and `filters` are for the tests' tiny network."""
    units = tuple(units or UNITS[int(num_layers)])
    x = net.cast_data(x)

    def stem(x):
        x = net.batchnorm('bn_data', x, EPS, fix_gamma=True)
        x = net.conv('conv0', x, filters[0], (7, 7), (2, 2), (3, 3))
        x = net.relu(net.batchnorm('bn0', x, EPS))
        return net.pool(x, (3, 3), (2, 2), (1, 1), 'max')

    x = net.block(stem, x)
    for i, n in enumerate(units):
        for j in range(n):
            stride = (1, 1) if (i == 0 or j > 0) else (2, 2)
            name = 'stage%d_unit%d' % (i + 1, j + 1)
            x = net.block(
                lambda x, j=j, stride=stride, name=name, i=i: _unit(
                    net, x, filters[i + 1], stride, j > 0, name), x)
    x = net.relu(net.batchnorm('bn1', x, EPS))
    return net.dense('fc1', net.global_avg_pool(x), num_classes)
