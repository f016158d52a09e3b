# Pallas TPU kernels, each as <name>/kernel.py plus a pure-jnp oracle in
# <name>/ref.py. Callers pass ``interpret=True`` explicitly off the chip;
# nothing here picks interpret mode for them.
