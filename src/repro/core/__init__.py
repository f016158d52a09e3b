"""MSched core: proactive memory scheduling for accelerator multitasking."""
