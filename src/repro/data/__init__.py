from repro.data.pipeline import (KINDS, DataConfig, SyntheticLM,  # noqa: F401
                                 make_iterator)
