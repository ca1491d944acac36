"""Plain float32 references: the mathematics a configuration stands for,
written without the program's modules, kernels or remat policies."""
