"""The ``nemotron`` family: NVIDIA-Nemotron-3-Nano-30B-A3B's block (Mamba-2
state-space mixers, attention and two-matrix relu² experts, each layer ONE
part alone under one norm) as a token-window Q-network."""
