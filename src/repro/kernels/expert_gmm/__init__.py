from repro.kernels.expert_gmm.ops import expert_gmm  # noqa
