from chipbench.families.LlamaForCausalLM import *  # noqa: F401,F403
