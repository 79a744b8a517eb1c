from .api import (  # noqa: F401
    to_static, not_to_static, ignore_module, TracedLayer, TranslatedLayer,
    save, load, InputSpec)
from .train_step import TrainStep, train_step_stats  # noqa: F401


class ProgramTranslator:
    """dy2static controller singleton (reference:
    dygraph_to_static/program_translator.py ProgramTranslator): a global
    enable/disable switch the @to_static machinery consults."""

    _instance = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
            cls._instance.enable_to_static = True
        return cls._instance

    @classmethod
    def get_instance(cls):
        return cls()

    def enable(self, enable_to_static):
        self.enable_to_static = bool(enable_to_static)


_CODE_LEVEL = 0
_VERBOSITY = 0


def set_code_level(level=100, also_to_stdout=False):
    """Reference: dygraph_to_static/logging_utils.py set_code_level —
    controls transformed-code dumping."""
    global _CODE_LEVEL
    _CODE_LEVEL = level


def set_verbosity(level=0, also_to_stdout=False):
    """Reference: dygraph_to_static/logging_utils.py set_verbosity."""
    global _VERBOSITY
    _VERBOSITY = level
