"""The llama family's counts against counts made by hand at phi3-mini's
shapes and at MiniCPM-2B's (a tied head, head size 64)."""
from chipbench import flops, spec

# openbmb/MiniCPM-2B-sft-bf16 config.json, the keys the counts read; its
# stack costs what a llama stack of these shapes costs (its muP scalars
# add no matmul), so the llama family counts it, named here: the
# benchmark has no MiniCPM family module
MINICPM_2B = {"architectures": ["MiniCPMForCausalLM"],
              "num_hidden_layers": 40, "hidden_size": 2304,
              "num_attention_heads": 36, "num_key_value_heads": 36,
              "head_dim": 64, "intermediate_size": 5760,
              "vocab_size": 122753, "tie_word_embeddings": True,
              "torch_dtype": "bfloat16"}


def model(name):
    if name == "minicpm-2b":
        return MINICPM_2B
    return spec.cell("phi3-mini.longdoc")["model"]


def fam(m):
    if m is MINICPM_2B:
        return spec.family({"architectures": ["LlamaForCausalLM"]})
    return spec.family(m)


def test_parameter_counts():
    m = model("minicpm-2b")
    # per layer: 4 x 2304^2 attention + 3 x 2304 x 5760 MLP
    per_layer = 4 * 2304 * 2304 + 3 * 2304 * 5760
    assert fam(m).layer_params(m) == per_layer == 61_046_784
    assert fam(m).params(m) == 40 * (per_layer + 2 * 2304) \
        + 122753 * 2304 + 2304
    assert abs(fam(m).params(m) / 1e9 - 2.7245) < 1e-3
    p = model("phi3-mini")
    per_layer = 4 * 3072 * 3072 + 3 * 3072 * 8192
    assert fam(p).layer_params(p) == per_layer == 113_246_208
    assert fam(p).params(p) == 32 * (per_layer + 2 * 3072) \
        + 2 * 32064 * 3072 + 3072
    assert abs(fam(p).params(p) / 1e9 - 3.8211) < 1e-3


def test_kv_bytes():
    # 2 (k, v) x layers x kv heads x head dim x 2 bytes
    m, p = model("minicpm-2b"), model("phi3-mini")
    assert fam(m).kv_bytes_per_token(m) == 2 * 40 * 36 * 64 * 2 == 368_640
    assert fam(p).kv_bytes_per_token(p) == 2 * 32 * 32 * 96 * 2 == 393_216


def test_decode_and_prefill_flops():
    m = model("minicpm-2b")
    # one token at context 1000: 2 x layer params x 40, attention
    # 4 x 36 x 64 x 1000 keys x 40 layers, head 2 x 2304 x 122753
    want = (2 * 61_046_784 * 40 + 4 * 36 * 64 * 1000 * 40
            + 2 * 2304 * 122753)
    assert fam(m).decode_flops(m, 1000) == want
    p = model("phi3-mini")
    # a 512-token chunk after 1024 cached tokens: keys 512*1024 +
    # 512*513/2, head once
    keys = 512 * 1024 + 512 * 513 // 2
    want = (512 * 2 * 113_246_208 * 32 + 4 * 32 * 96 * keys * 32
            + 2 * 3072 * 32064)
    assert fam(p).prefill_flops(p, 1024, 512) == want


def test_chunk_kernel_and_roofline():
    p = model("phi3-mini")
    k = fam(p).chunk_kernel(p, 1024, 512)
    keys = 512 * 1024 + 512 * 513 // 2
    assert k["flops"] == 4 * 32 * 96 * keys * 32
    # q and out (512 x 32 x 96 x 2 B each), prefix k/v read, chunk k/v
    # read and written, per layer
    per_layer = 2 * 512 * 32 * 96 * 2 + 1024 * 2 * 32 * 96 * 2 \
        + 2 * 512 * 2 * 32 * 96 * 2
    assert k["bytes"] == 32 * per_layer
    peak = spec.peaks("TPU v5 lite")
    t = flops.least_time(k["flops"], k["bytes"], peak)
    assert t == max(k["flops"] / 197e12, k["bytes"] / 819e9)
    m = model("minicpm-2b")
    b = fam(m).decode_step_bytes(m, [100, 200])
    assert b == fam(m).weight_bytes(m) + 300 * 368_640
    assert fam(m).weight_bytes(m) == 2 * (40 * (61_046_784 + 2 * 2304)
                                         + 122753 * 2304 + 2304)

