"""Llama-type dense decoders (deepseek-coder): the program serves them on
the same dense path as BitNet, so ``bitnet.py`` reads them."""
SAME_AS = "bitnet"
