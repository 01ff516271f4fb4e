"""The tests' plain reference for the 2-conv MNIST CNN: the MedCNN
reference's code over other stage widths, added as a file of its own."""

import importlib.util
import os

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
                    "..", "benchmarks", "reference", "medcnn.py")
_spec = importlib.util.spec_from_file_location("_tiny_smallcnn_ref", _SRC)
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)
_mod.FEATURES, _mod.DENSE = (32, 64), (128,)
init, forward, loss, forward_flops = (
    _mod.init, _mod.forward, _mod.loss, _mod.forward_flops)
