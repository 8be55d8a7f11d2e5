"""An architecture that a checkout gains as a file alone (``cells.py``
puts it in ``arch/``): the dense decoder of ``arch/dense.py`` under
another name, every part of the interface that ``spec.py`` lists taken
from it, so that a configuration naming ``"arch": "dense_alias"`` is
built, served and checked from data alone."""
from pathlib import Path

import spec

_dense = spec.load(Path(__file__).with_name("dense.py"), "arch")

Dims = _dense.Dims
model_config = _dense.model_config
program_params = _dense.program_params
layer_keys = _dense.layer_keys
layer_weights = _dense.layer_weights
layer = _dense.layer
embed = _dense.embed
head = _dense.head
