"""Architecture registry mapping config.architecture -> (init, forward)."""

from typing import Callable, Tuple

from production_stack_tpu.engine.config import ModelConfig


def get_model(config: ModelConfig) -> Tuple[Callable, Callable]:
    """Returns (init_params, forward) for the configured architecture."""
    arch = config.architecture
    if arch in ("llama", "mistral", "qwen2"):
        from production_stack_tpu.models import llama
        return llama.init_params, llama.forward
    if arch == "opt":
        from production_stack_tpu.models import opt
        return opt.init_params, opt.forward
    if arch == "gpt2":
        from production_stack_tpu.models import gpt2
        return gpt2.init_params, gpt2.forward
    if arch == "mixtral":
        from production_stack_tpu.models import mixtral
        return mixtral.init_params, mixtral.forward
    if arch == "qwen3_next":
        from production_stack_tpu.models import qwen3_next
        return qwen3_next.init_params, qwen3_next.forward
    raise ValueError(f"Unknown architecture: {arch}")


def list_architectures():
    return ["llama", "mistral", "qwen2", "opt", "gpt2", "mixtral",
            "qwen3_next"]
