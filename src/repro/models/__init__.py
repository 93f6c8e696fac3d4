"""Model definitions: the diffusion backbones the engine serves
(``diffusion``, ``sampler``, ``vae``) and an LM training stack (``lm``,
``attention``, ``mamba``, ``moe``, ``flash``) that the serving path does not
use. Import each submodule by name."""
