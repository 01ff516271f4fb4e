"""Data (`data/synthetic.make_dataset`, `data/folder.load_folder_splits`):
seconds of set-up spent making or loading the images on the host, the sum
of the `hefl.setup.data` spans that ended before the window opened."""

import span_metrics as sm


def read(record, trace):
    return sm.setup_sum_s(lambda name: name == sm.SETUP_STEP + "data")
